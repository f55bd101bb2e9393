"""The per-scope reduction of a trace (bench/scopes.py), on the CPU: by
hand on a made-up reduction, and on the HLO modules of a CPU trace."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import scopes  # noqa: E402


def made_up():
    # window 0..200 ns, one chip. The scan's while (10-150) encloses the
    # round: grad 10-40, the pads 40-50 and the kernel 50-90 under
    # dwfl.mix, telemetry 90-100, an unscoped convert 100-120; the chunk's
    # epilogue (telemetry) 160-170 lies outside the while.
    ops = [(10.0, 150.0, "%while.4 = (s32[]) while(...)", "unscoped"),
           (10.0, 40.0, "%fusion.1 = f32[10,8] fusion(...)", "dwfl.grad"),
           (40.0, 50.0, "%pad.2 = f32[16,512] pad(...)", "dwfl.mix"),
           (50.0, 90.0, "%dp_mix_round.7 = f32[1,16,512] custom-call(...)",
            "dwfl.mix"),
           (90.0, 100.0, "%reduce.3 = f32[] reduce(...)", "dwfl.telemetry"),
           (100.0, 120.0, "%convert.39 = f32[20,8] convert(...)",
            "unscoped"),
           (160.0, 170.0, "%fusion.9 = f32[5,7] fusion(...)",
            "dwfl.telemetry")]
    harness = [(0.0, 150.0, "bench.dispatch"), (150.0, 200.0, "bench.wait")]
    program = [(2.0, 8.0, "dwfl.chunk.dispatch")]
    return scopes.Reduced((0.0, 200.0), [ops], harness, program)


def test_scope_of_is_the_innermost_dwfl_scope():
    assert scopes.scope_of(
        "jit(program)/while/body/closed_call/dwfl.grad/vmap(jvp())/dot"
    ) == "dwfl.grad"
    assert scopes.scope_of(
        "jit(program)/dwfl.mix/jit(dp_mix_round)/dwfl.mix/pad") == "dwfl.mix"
    assert scopes.scope_of("jit(program)/dwfl.telemetry/add;jit(program)"
                           "/dwfl.grad/mul") == "dwfl.telemetry"
    assert scopes.scope_of("jit(program)/while/body/convert") == "unscoped"
    assert scopes.scope_of("") == "unscoped"


def test_instruction_is_the_event_name_up_to_its_text():
    assert scopes.instruction("%fusion.13 = f32[2] fusion(%a)") == "fusion.13"
    assert scopes.instruction("dot_general.1") == "dot_general.1"


def test_containers_are_not_leaves():
    r = made_up()
    names = [n.split()[0] for _, _, n, _ in scopes.leaves(r.devices[0])]
    assert "%while.4" not in names and len(names) == 6


def test_scopes_kernel_and_remainder_add_up_to_the_leaf_time_by_hand():
    r = made_up()
    sec = scopes.scope_seconds(r)
    assert sec == pytest.approx({
        "dwfl.grad": 30e-9, "dwfl.mix": 10e-9, "dp_mix_round": 40e-9,
        "dwfl.telemetry": 20e-9, "unscoped": 20e-9})
    t = scopes.table(r, rounds=2)
    assert t["ms_per_round"]["dwfl.grad"] == pytest.approx(1e3 * 30e-9 / 2)
    # the while's own 140 ns is not counted again
    assert t["leaf_ms"] == pytest.approx(1e3 * 120e-9 / 2)
    assert t["unscoped_share"] == pytest.approx(20.0 / 120.0)
    assert t["top_ops"]["unscoped"][0][0].startswith("%convert.39")


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
    mods = scopes.hlo_modules(path.read_bytes())
    (key,) = [k for k in mods if k.startswith("jit_f(")]
    found = {scopes.scope_of(o) for o in mods[key].values()}
    assert {"dwfl.grad", "dwfl.mix"} <= found
    dots = [i for i, o in mods[key].items() if o.endswith("/dot_general")]
    assert dots and all(scopes.scope_of(mods[key][i]) == "dwfl.grad"
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
    return scopes.reduce(FIXTURE)


def test_the_readers_read_the_fixture_as_its_run_did():
    import json
    from bench import trace
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    paper = json.loads((ROOT / "bench/configs/dwfl-paper-n10-static.json")
                       .read_text())
    ctx = {"cfg": paper, "peak": run.peaks_for("TPU v5 lite"), "chips": 1,
           "rounds": FIXTURE_ROUNDS, "chunks": FIXTURE_CHUNKS,
           "flops": run.load_module(ROOT / "bench/flops/mlp.py")}
    s = trace.summarize(trace.load(FIXTURE))
    for name, want in FIXTURE_READ.items():
        got = run.load_module(ROOT / "bench/metrics" / f"{name}.py").read(
            s, ctx)
        assert got == pytest.approx(want, rel=1e-12), name


def test_every_scope_and_the_kernel_are_found_in_the_fixture(chip_trace):
    from bench import trace
    sec = scopes.scope_seconds(chip_trace)
    assert set(sec) == {*scopes.SCOPES, scopes.KERNEL_MATCH, scopes.UNSCOPED}
    # the kernel apart is what dp_mix_roofline reads
    s = trace.summarize(trace.load(FIXTURE))
    assert sec[scopes.KERNEL_MATCH] == pytest.approx(
        sum(trace.op_totals(s, scopes.KERNEL_MATCH).values()))
    # the parts add up to the leaf ops, which leave the scan's while out
    ops = chip_trace.devices[0]
    leaf = scopes.leaves(ops)
    assert any(n.startswith("%while") for _, _, n, _ in ops)
    assert not any(n.startswith("%while") for _, _, n, _ in leaf)
    assert sum(sec.values()) == pytest.approx(
        sum(b - a for a, b, _, _ in leaf) * 1e-9)
    t = scopes.table(chip_trace, FIXTURE_ROUNDS)
    assert sum(t["ms_per_round"].values()) == pytest.approx(t["leaf_ms"])
    assert 0.0 < t["unscoped_share"] < 0.5


def test_the_fixture_holds_the_program_spans(chip_trace):
    names = {n for _, _, n in chip_trace.program}
    assert names == {"dwfl.chunk.dispatch"}
    assert len(chip_trace.program) == FIXTURE_CHUNKS
    # each dispatch span lies inside a harness dispatch span
    for a, b, _ in chip_trace.program:
        assert any(x <= a and b <= y for x, y, n in chip_trace.harness
                   if n == "bench.dispatch")
