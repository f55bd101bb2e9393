"""The per-scope reduction of a trace (bench/trace.py's scopes and
bench/scopes.py's table over them), on the CPU: by hand on a made-up
summary, on the HLO modules of a CPU trace, and on a chip trace kept as a
fixture."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import scopes, trace  # noqa: E402


def made_up():
    # window 0..200 ns, one chip. The scan's while (10-150) encloses the
    # round: grad 10-40, the pads 40-50 and the kernel 50-90 under
    # dwfl.mix, telemetry 90-100, an unscoped convert 100-120; the chunk's
    # epilogue (telemetry) 160-170 lies outside the while.
    Op = trace.Op
    ops = [Op(10.0, 150.0, "%while.4 = (s32[]) while(...)", "unscoped"),
           Op(10.0, 40.0, "%fusion.1 = f32[10,8] fusion(...)", "dwfl.grad"),
           Op(40.0, 50.0, "%pad.2 = f32[16,512] pad(...)", "dwfl.mix"),
           Op(50.0, 90.0, "%dp_mix_round.7 = f32[1,16,512] custom-call(...)",
              "dwfl.mix"),
           Op(90.0, 100.0, "%reduce.3 = f32[] reduce(...)",
              "dwfl.telemetry"),
           Op(100.0, 120.0, "%convert.39 = f32[20,8] convert(...)",
              "unscoped"),
           Op(160.0, 170.0, "%fusion.9 = f32[5,7] fusion(...)",
              "dwfl.telemetry")]
    harness = [(0.0, 150.0, "bench.dispatch"), (150.0, 200.0, "bench.wait")]
    program = [(2.0, 8.0, "dwfl.chunk.dispatch")]
    return trace.Summary((0.0, 200.0), [trace.mark_containers(ops)],
                         harness, program)


def test_scope_of_is_the_innermost_dwfl_scope():
    assert trace.scope_of(
        "jit(program)/while/body/closed_call/dwfl.grad/vmap(jvp())/dot"
    ) == "dwfl.grad"
    assert trace.scope_of(
        "jit(program)/dwfl.mix/jit(dp_mix_round)/dwfl.mix/pad") == "dwfl.mix"
    assert trace.scope_of("jit(program)/dwfl.telemetry/add;jit(program)"
                          "/dwfl.grad/mul") == "dwfl.telemetry"
    assert trace.scope_of("jit(program)/while/body/convert") == "unscoped"
    assert trace.scope_of("") == "unscoped"


def test_instruction_is_the_event_name_up_to_its_text():
    assert trace.instruction("%fusion.13 = f32[2] fusion(%a)") == "fusion.13"
    assert trace.instruction("dot_general.1") == "dot_general.1"


def test_containers_are_not_leaves():
    r = made_up()
    names = [o.name.split()[0] for o in trace.leaves(r.devices[0])]
    assert "%while.4" not in names and len(names) == 6
    # the marks keep the chip's order of ops
    assert [o.name for o in r.devices[0]][0].startswith("%while.4")


def test_scopes_kernel_and_remainder_add_up_to_the_leaf_time_by_hand():
    r = made_up()
    sec = scopes.split(r)
    assert sec == pytest.approx({
        "dwfl.grad": 30e-9, "dwfl.mix": 10e-9, "dp_mix_round": 40e-9,
        "dwfl.telemetry": 20e-9, "unscoped": 20e-9})
    t = scopes.table(r, rounds=2)
    assert t["ms_per_round"]["dwfl.grad"] == pytest.approx(1e3 * 30e-9 / 2)
    # the while's own 140 ns is not counted again
    assert t["leaf_ms"] == pytest.approx(1e3 * 120e-9 / 2)
    assert t["unscoped_share"] == pytest.approx(20.0 / 120.0)
    assert t["top_ops"]["unscoped"][0][0].startswith("%convert.39")


def test_scope_seconds_reads_a_scope_and_its_sub_scopes_by_hand():
    r = made_up()
    assert trace.scope_seconds(r, "dwfl.grad") == pytest.approx(30e-9)
    assert trace.scope_seconds(r, "dwfl.mix") == pytest.approx(50e-9)
    assert trace.scope_seconds(r, "dwfl.mix", exclude="dp_mix_round"
                               ) == pytest.approx(10e-9)
    # the while's 140 ns is a container's and is not counted
    assert trace.scope_seconds(r, "unscoped") == pytest.approx(20e-9)
    Op = trace.Op
    r.devices[0] += [Op(180.0, 185.0, "%dot.5", "dwfl.grad.attn"),
                     Op(185.0, 187.0, "%dot.6", "dwfl.gradient")]
    assert trace.scope_seconds(r, "dwfl.grad") == pytest.approx(35e-9)
    # two chips: the mean over them
    r.devices.append([Op(0.0, 10.0, "%fusion.1", "dwfl.grad")])
    assert trace.scope_seconds(r, "dwfl.grad") == pytest.approx(45e-9 / 2)


def test_the_table_counts_sub_scopes_as_the_readers_do():
    """A sub-scope (``dwfl.grad.attn``) is its scope's, in the table as in
    ``trace.scope_seconds``; a ``dwfl.*`` scope under none of the table's
    is a part of its own, so the parts still add up to the leaf time."""
    r = made_up()
    Op = trace.Op
    r.devices[0] += [Op(180.0, 185.0, "%dot.5", "dwfl.grad.attn"),
                     Op(185.0, 187.0, "%dot.6", "dwfl.route"),
                     Op(187.0, 188.0, "%dot.7", "dwfl.route.topk")]
    sec = scopes.split(r)
    assert sec["dwfl.grad"] == pytest.approx(
        trace.scope_seconds(r, "dwfl.grad")) == pytest.approx(35e-9)
    assert sec["dwfl.route"] == pytest.approx(3e-9)
    assert "dwfl.route.topk" not in sec
    assert sum(sec.values()) == pytest.approx(128e-9)
    assert [n for n, _ in scopes.top_ops(r, "dwfl.grad")] == [
        "%fusion.1 = f32[10,8] fusion(...)", "%dot.5"]


def test_idle_gaps_name_the_program_span_inside_the_harness_span():
    gaps = scopes.gap_labels(made_up())
    # idle 0-10 (dispatch, with the program's dispatch span), 150-160
    # and 170-200 (wait); the while keeps the chip busy to 150
    assert gaps[0] == ["bench.wait", pytest.approx(30e-9)]
    assert ["bench.dispatch>dwfl.chunk.dispatch",
            pytest.approx(10e-9)] in gaps
    assert len(gaps) == 3


def test_scopes_are_read_from_the_hlo_modules_of_a_trace(tmp_path):
    """The trace's metadata plane holds the optimized modules that ran;
    each instruction, fusions included, keeps its scope path."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("dwfl.grad"):
            y = jnp.sin(x) @ x
        with jax.named_scope("dwfl.mix"):
            return jnp.cos(y) + 1.0

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    mods = trace.hlo_modules(path.read_bytes())
    (key,) = [k for k in mods if k.startswith("jit_f(")]
    found = {trace.scope_of(o) for o in mods[key].values()}
    assert {"dwfl.grad", "dwfl.mix"} <= found
    dots = [i for i, o in mods[key].items() if o.endswith("/dot_general")]
    assert dots and all(trace.scope_of(mods[key][i]) == "dwfl.grad"
                        for i in dots)


# ------------------------------------------- a chip trace, kept as a fixture

# bench/fixtures/paper_n10.curve.xplane.pb.gz: the raw trace of a 0.5 s
# `paper_n10.curve` window (--trace 1 --keep-trace, seed 2147483912, TPU v5
# lite): 215 rounds in 43 chunks. The run's own result line read these.
FIXTURE = ROOT / "bench/fixtures/paper_n10.curve.xplane.pb.gz"
FIXTURE_ROUNDS, FIXTURE_CHUNKS = 215, 43
FIXTURE_READ = {"step_mfu_pct": 0.24801172543687922,
                "dp_mix_roofline": 13.08787119115343,
                "device_idle_pct": 21.05964912195174,
                "driver_host_ms": 1.1806665813953487}


@pytest.fixture(scope="module")
def chip_trace():
    return trace.read(FIXTURE)


# bench/scopes.py's table of the fixture at the parent of the change that
# moved the scopes into the trace's Summary, ms per round
FIXTURE_SCOPES = {"dwfl.grad": 0.14404539069767247,
                  "dwfl.mix": 0.17112167441860437,
                  "dwfl.telemetry": 0.1819992558139521}
SCOPE_READERS = {"grad_pass_ms": "dwfl.grad", "mix_wrap_ms": "dwfl.mix",
                 "telemetry_ms": "dwfl.telemetry"}


def reader(name):
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    return run.load_module(ROOT / "bench/metrics" / f"{name}.py")


def fixture_ctx():
    import json
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    paper = json.loads((ROOT / "bench/configs/dwfl-paper-n10-static.json")
                       .read_text())
    return {"cfg": paper, "peak": run.peaks_for("TPU v5 lite"), "chips": 1,
            "rounds": FIXTURE_ROUNDS, "chunks": FIXTURE_CHUNKS,
            "flops": run.load_module(ROOT / "bench/flops/mlp.py")}


def test_the_readers_read_the_fixture_as_its_run_did():
    s = trace.summarize(trace.load(FIXTURE))
    for name, want in FIXTURE_READ.items():
        got = reader(name).read(s, fixture_ctx())
        assert got == pytest.approx(want, rel=1e-12), name


def test_the_readers_read_the_scoped_fixture_as_its_run_did(chip_trace):
    """The Summary a run reads now, with scopes and leaf marks, leaves
    every earlier reader where it was."""
    for name, want in FIXTURE_READ.items():
        got = reader(name).read(chip_trace, fixture_ctx())
        assert got == pytest.approx(want, rel=1e-12), name
    s = trace.summarize(trace.load(FIXTURE))
    assert trace.busy_ns(chip_trace) == trace.busy_ns(s)
    assert trace.op_totals(chip_trace) == trace.op_totals(s)
    assert trace.idle_gaps(chip_trace) == trace.idle_gaps(s)


def test_the_scope_readers_equal_the_scope_table(chip_trace):
    t = scopes.table(chip_trace, FIXTURE_ROUNDS)["ms_per_round"]
    for name, scope in SCOPE_READERS.items():
        got = reader(name).read(chip_trace, fixture_ctx())
        assert got == pytest.approx(t[scope], rel=1e-12), name
        assert got == pytest.approx(FIXTURE_SCOPES[scope], rel=1e-12), name


def test_the_scope_readers_the_kernel_and_the_rest_make_the_leaf_total(
        chip_trace):
    ms = sum(reader(name).read(chip_trace, fixture_ctx())
             for name in SCOPE_READERS)
    ms += 1e3 * (trace.scope_seconds(chip_trace, trace.UNSCOPED)
                 + sum(trace.op_totals(chip_trace,
                                       trace.KERNEL_MATCH).values())
                 ) / FIXTURE_ROUNDS
    t = scopes.table(chip_trace, FIXTURE_ROUNDS)
    assert ms == pytest.approx(t["leaf_ms"], rel=1e-12)


def test_the_scope_readers_read_nothing_without_scopes():
    s = trace.summarize(trace.load(FIXTURE))        # no modules: unscoped
    assert all(reader(n).read(s, fixture_ctx()) is None
               for n in SCOPE_READERS)


def test_the_breakdown_leaves_containers_out(chip_trace):
    ops = trace.breakdown(chip_trace)["device_ops"]
    assert not any(n.startswith("%while") for n, _ in ops)
    assert ops[0][0].startswith("%dp_mix_round")
    # the container is there, and its time is its body's
    whiles = trace.op_totals(chip_trace, "%while")
    assert whiles and max(whiles.values()) > ops[0][1]


def test_every_scope_and_the_kernel_are_found_in_the_fixture(chip_trace):
    sec = scopes.split(chip_trace)
    assert set(sec) == {*scopes.SCOPES, trace.KERNEL_MATCH, trace.UNSCOPED}
    # the kernel apart is what dp_mix_roofline reads
    s = trace.summarize(trace.load(FIXTURE))
    assert sec[trace.KERNEL_MATCH] == pytest.approx(
        sum(trace.op_totals(s, trace.KERNEL_MATCH).values()))
    # the parts add up to the leaf ops, which leave the scan's while out
    ops = chip_trace.devices[0]
    leaf = trace.leaves(ops)
    assert any(o.name.startswith("%while") for o in ops)
    assert not any(o.name.startswith("%while") for o in leaf)
    assert sum(sec.values()) == pytest.approx(
        sum(o.end - o.start for o in leaf) * 1e-9)
    t = scopes.table(chip_trace, FIXTURE_ROUNDS)
    assert sum(t["ms_per_round"].values()) == pytest.approx(t["leaf_ms"])
    assert 0.0 < t["unscoped_share"] < 0.5


def test_the_fixture_holds_the_program_spans(chip_trace):
    names = {n for _, _, n in chip_trace.program}
    assert names == {"dwfl.chunk.dispatch"}
    assert len(chip_trace.program) == FIXTURE_CHUNKS
    # each dispatch span lies inside a harness dispatch span
    for a, b, _ in chip_trace.program:
        assert any(x <= a and b <= y for x, y, n in chip_trace.spans
                   if n == "bench.dispatch")
