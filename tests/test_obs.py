"""repro.obs — run logs, watchdogs, retrace guard, telemetry helpers
(ISSUE 6). The in-scan telemetry's trajectory invariants live in
tests/test_trajectory.py; this file covers the host half plus the pure
telemetry math, and ends with the end-to-end quickstart acceptance: a
runlog-enabled train run whose JSONL ε trajectory matches the host-side
epsilon_report.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import privacy
from repro.obs import report as report_lib
from repro.obs import telemetry as tl


# ---------------------------------------------------------------------------
# TelemetrySpec + pure telemetry math
# ---------------------------------------------------------------------------


def test_spec_fields_order_and_pack_unpack():
    spec = obs.TelemetrySpec()
    assert spec.fields == ("loss", "grad_norm", "consensus", "snr_db",
                           "deep_fade", "participation", "epsilon")
    vals = {f: float(i) for i, f in enumerate(spec.fields)}
    arr = spec.pack(vals)
    assert arr.shape == (spec.n_fields,) and arr.dtype == jnp.float32
    back = spec.unpack(arr)
    for f in spec.fields:
        assert float(back[f]) == vals[f]
    with pytest.raises(ValueError):
        spec.unpack(jnp.zeros((3,)))
    # hashable / usable as a static jit argument
    assert hash(spec) == hash(obs.TelemetrySpec())


def test_consensus_distance_matches_numpy_reference():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(6, 31)).astype(np.float32)
    ref = np.sqrt(np.mean(np.sum((x - x.mean(0)) ** 2, axis=-1)))
    np.testing.assert_allclose(float(tl.consensus_distance(jnp.asarray(x))),
                               ref, rtol=1e-5)
    # pytree of leaves == one concatenated buffer
    tree = {"a": jnp.asarray(x[:, :10]), "b": jnp.asarray(x[:, 10:])}
    np.testing.assert_allclose(float(tl.consensus_distance(tree)),
                               ref, rtol=1e-5)
    # fleet layout: worker_axis=1 returns one distance per replicate
    xr = rng.normal(size=(3, 6, 31)).astype(np.float32)
    got = np.asarray(tl.consensus_distance(jnp.asarray(xr), worker_axis=1))
    refr = np.sqrt(np.mean(np.sum(
        (xr - xr.mean(1, keepdims=True)) ** 2, axis=-1), axis=-1))
    np.testing.assert_allclose(got, refr, rtol=1e-5)


def test_consensus_distance_no_cancellation_near_consensus():
    """The direct subtract-then-square form must not collapse to 0 near
    consensus — the regime the telemetry exists to watch. (Gram / norm
    identity forms do: mean‖x‖² − ‖x̄‖² loses all signal in f32 here.)"""
    rng = np.random.default_rng(1)
    base = rng.normal(size=(21258,)).astype(np.float32)
    x = base[None] + 1e-4 * rng.normal(size=(8, 21258)).astype(np.float32)
    got = float(tl.consensus_distance(jnp.asarray(x)))
    ref = float(np.sqrt(np.mean(np.sum(
        (x.astype(np.float64) - x.astype(np.float64).mean(0)) ** 2, -1))))
    assert ref > 1e-3                       # there IS signal at this scale
    np.testing.assert_allclose(got, ref, rtol=1e-3)
    # the identity form (what consensus_distance must NOT do) collapses
    ident = np.mean(np.sum(x ** 2, -1)) - np.sum(x.mean(0) ** 2)
    assert not np.isclose(max(ident, 0.0), ref ** 2, rtol=0.5)


def test_channel_scalars_crafted_channel():
    """participation/deep_fade/snr on a hand-built channel + W."""
    n = 4
    from repro.net.state import TracedChannelState
    chan = TracedChannelState(
        h=jnp.asarray([1.0, 1.0, 1.0, 0.001], jnp.float32),  # worker 3 faded
        P=jnp.ones((n,), jnp.float32), alpha=jnp.ones((n,), jnp.float32),
        beta=jnp.ones((n,), jnp.float32), c=jnp.float32(1.0),
        sigma=jnp.float32(0.5), sigma_m=jnp.float32(0.1), n_workers=n)
    spec = obs.TelemetrySpec()
    # W: worker 3 hears nobody (silent row) -> participation 3/4
    W = np.full((n, n), 0.25, np.float32)
    W[3, :] = 0.0
    W[3, 3] = 1.0
    np.fill_diagonal(W[:3, :3], 0.25)
    vals = chan.telemetry(spec, jnp.asarray(W))
    assert float(vals["participation"]) == pytest.approx(0.75)
    assert float(vals["deep_fade"]) == pytest.approx(0.25)  # 1e-6 << median
    assert np.isfinite(float(vals["snr_db"]))
    # complete graph default: everyone listens
    vals_full = chan.telemetry(spec)
    assert float(vals_full["participation"]) == 1.0


def test_epsilon_round_matches_privacy_traced():
    from repro.core import protocol as P
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=6, p_dbm=60.0,
                             sigma=0.8, channel_model="dynamic",
                             scenario="iot_dense")
    sim = proto.simulator()
    net = sim.init(jax.random.PRNGKey(0))
    _net, chan, _mask, W = sim.round(jax.random.PRNGKey(1), net)
    got = float(tl.epsilon_round(proto, chan, W))
    ref = np.asarray(privacy.epsilon_dwfl_traced(
        proto.gamma, proto.clip, chan, proto.delta, W))
    assert got == pytest.approx(float(ref.max()), rel=1e-6)


def test_eps_moments_compose_like_heterogeneous():
    """compose_from_moments(Σ moments) == compose_heterogeneous(eps list),
    the scan-carry accumulator's contract — now on the WIDENED [4+A]
    layout carrying the per-order RDP ledger (ISSUE 10)."""
    from repro.core import accounting
    rng = np.random.default_rng(2)
    eps_list = rng.uniform(0.01, 0.4, size=37)
    rho_list = accounting.rho_from_epsilon(eps_list, 1e-5)
    orders = np.asarray(accounting.ORDER_GRID)
    acc = tl.init_eps_moments()
    for e, r in zip(eps_list, rho_list):
        acc = tl.accumulate_eps(acc, jnp.float32(e),
                                rdp=jnp.asarray(orders * r, jnp.float32))
    assert np.asarray(acc).shape == (4 + accounting.N_ORDERS,)
    assert int(np.asarray(acc)[3]) == 37
    e_m, d_m = privacy.compose_from_moments(np.asarray(acc), 1e-5)
    e_ref, d_ref = privacy.compose_heterogeneous(eps_list, 1e-5)
    np.testing.assert_allclose(e_m, e_ref, rtol=1e-4)
    np.testing.assert_allclose(d_m, d_ref, rtol=1e-8)
    # the appended ledger block converts through the rdp dispatch and is
    # tighter than the composition quote on this trajectory
    e_r, d_r = privacy.compose_from_moments(np.asarray(acc), 1e-5,
                                            accountant="rdp")
    e_want, _ = accounting.rdp_to_epsilon(orders * rho_list.sum(), d_r)
    np.testing.assert_allclose(e_r, e_want, rtol=1e-4)
    assert e_r < e_m and d_r == pytest.approx(37 * 1e-5 + 1e-6)
    e_min, _ = privacy.compose_from_moments(np.asarray(acc), 1e-5,
                                           accountant="min")
    assert e_min == pytest.approx(min(e_m, e_r))
    # legacy narrow [4] accumulators still work, and the layouts guard
    # each other: rdp into [4] / missing rdp on [4+A] / rdp dispatch on [4]
    acc4 = tl.init_eps_moments(n_orders=0)
    acc4 = tl.accumulate_eps(acc4, jnp.float32(0.2))
    assert np.asarray(acc4).shape == (4,)
    with pytest.raises(ValueError):
        tl.accumulate_eps(acc4, jnp.float32(0.2),
                          rdp=jnp.asarray(orders, jnp.float32))
    with pytest.raises(ValueError):
        tl.accumulate_eps(acc, jnp.float32(0.2))
    with pytest.raises(ValueError):
        privacy.compose_from_moments(np.asarray(acc4), 1e-5,
                                     accountant="rdp")
    # batched (fleet) accumulators compose per replicate
    accR = tl.init_eps_moments(replicates=3)
    accR = tl.accumulate_eps(
        accR, jnp.asarray([0.1, 0.2, 0.3], jnp.float32),
        rdp=jnp.asarray(orders[None]
                        * np.asarray(accounting.rho_from_epsilon(
                            np.asarray([0.1, 0.2, 0.3]), 1e-5))[:, None],
                        jnp.float32))
    e_b, d_b = privacy.compose_from_moments(np.asarray(accR), 1e-5)
    assert e_b.shape == (3,) and (np.diff(e_b) > 0).all()
    e_bR, _ = privacy.compose_from_moments(np.asarray(accR), 1e-5,
                                           accountant="rdp")
    assert e_bR.shape == (3,) and (np.diff(e_bR) > 0).all()
    with pytest.raises(ValueError):
        privacy.compose_from_moments(np.zeros((3,)), 1e-5)


# ---------------------------------------------------------------------------
# retrace_guard
# ---------------------------------------------------------------------------


def test_retrace_guard_clean_block_passes():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))                        # warmup
    with obs.retrace_guard(f, label="double") as g:
        for _ in range(3):
            f(jnp.ones((4,)))
    assert g.new_traces == 0 and g.total_traces == 1 and not g.violated


def test_retrace_guard_raises_on_shape_retrace():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))
    with pytest.raises(obs.RetraceError):
        with obs.retrace_guard(f):
            f(jnp.ones((5,)))                # new shape -> recompile
    # non-strict: records the violation, forwards it, does not raise
    seen = []
    f2 = jax.jit(lambda x: x + 1)
    f2(jnp.ones((2,)))
    with obs.retrace_guard(f2, strict=False, on_retrace=seen.append) as g:
        f2(jnp.ones((3,)))
    assert g.violated and g.new_traces == 1 and len(seen) == 1


def test_retrace_guard_rejects_non_jitted_and_empty():
    with pytest.raises(ValueError):
        obs.retrace_guard()
    with pytest.raises(TypeError):
        with obs.retrace_guard(lambda x: x):
            pass


def test_retrace_guard_never_masks_block_errors():
    f = jax.jit(lambda x: x)
    f(jnp.ones((1,)))
    with pytest.raises(RuntimeError, match="boom"):
        with obs.retrace_guard(f):
            f(jnp.ones((2,)))                # would violate...
            raise RuntimeError("boom")       # ...but the error wins


# ---------------------------------------------------------------------------
# RunLog + watchdogs
# ---------------------------------------------------------------------------


def test_runlog_manifest_and_events_roundtrip(tmp_path):
    rl = obs.RunLog.open(tmp_path / "r1", kind="test",
                         config={"b": 2, "a": 1}, seed=7, argv=["--x"])
    assert obs.RunLog.is_run_dir(rl.dir)
    man = obs.RunLog.read_manifest(rl.dir)
    assert man["kind"] == "test" and man["seed"] == 7
    assert man["status"] == "open"           # crashed-run indicator until close
    assert man["config_hash"] == obs.config_hash({"a": 1, "b": 2})  # sorted
    rl.round_metrics(0, loss=jnp.float32(1.5))
    rl.eval_metrics(0, eval_loss=2.0)
    rl.epsilon(0, eps_composed=0.1, eps_round=0.05)
    rl.warn("something odd", step=0)
    rl.close("ok", steps=1)
    man = obs.RunLog.read_manifest(rl.dir)
    assert man["status"] == "ok" and man["n_warnings"] == 1
    rounds = obs.RunLog.read_events(rl.dir, "round")
    assert rounds == [pytest.approx({"t": rounds[0]["t"], "type": "round",
                                     "step": 0, "loss": 1.5})]
    assert [e["type"] for e in obs.RunLog.read_events(rl.dir)] == [
        "round", "eval", "epsilon", "warning", "close"]
    rl.close("ignored")                      # idempotent
    assert obs.RunLog.read_manifest(rl.dir)["status"] == "ok"


def test_runlog_open_under_unique_dirs(tmp_path):
    a = obs.RunLog.open_under(tmp_path, kind="train")
    b = obs.RunLog.open_under(tmp_path, kind="train")
    assert a.dir != b.dir
    assert a.dir.name.startswith("train-")
    a.close()
    b.close("error")
    assert obs.RunLog.read_manifest(b.dir)["status"] == "error"


def test_eps_budget_watchdog_fires_once_each():
    warned = []
    dog = obs.EpsilonBudgetWatchdog(
        2.0, frac=0.8, on_warn=lambda msg, **kw: warned.append((msg, kw)))
    assert dog.check(1.0) == []
    fired = dog.check(1.7, step=10)          # crosses 80% of 2.0
    assert len(fired) == 1 and "80%" in fired[0]
    assert dog.check(1.8) == []              # fires only once
    fired = dog.check(2.5, step=20)
    assert len(fired) == 1 and "EXCEEDED" in fired[0]
    assert dog.check(99.0) == []
    assert len(warned) == 2 and warned[1][1]["step"] == 20
    # a jump straight past the budget fires both warnings at once
    dog2 = obs.EpsilonBudgetWatchdog(1.0)
    assert len(dog2.check(5.0)) == 2
    with pytest.raises(ValueError):
        obs.EpsilonBudgetWatchdog(0.0)
    with pytest.raises(ValueError):
        obs.EpsilonBudgetWatchdog(1.0, frac=1.5)


def test_retrace_watchdog_logs_compiles_then_warns(tmp_path):
    rl = obs.RunLog.open(tmp_path / "r", kind="test")
    f = jax.jit(lambda x: x * 3)
    dog = obs.RetraceWatchdog(f, runlog=rl, label="step")
    f(jnp.ones((2,)))
    assert dog.check(step=0) == 0            # warmup compile: info, not warning
    f(jnp.ones((2,)))
    assert dog.check(step=1) == 0
    f(jnp.ones((9,)))                        # retrace
    assert dog.check(step=2) == 1
    rl.close()
    assert len(obs.RunLog.read_events(rl.dir, "compile")) == 1
    warns = obs.RunLog.read_events(rl.dir, "warning")
    assert len(warns) == 1 and "retrace after warmup" in warns[0]["message"]
    with pytest.raises(ValueError):
        obs.RetraceWatchdog()


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------


def test_report_summarize_and_main(tmp_path, capsys):
    rl = obs.RunLog.open(tmp_path / "runs" / "r1", kind="train", seed=3)
    for t in range(4):
        rl.round_metrics(t, loss=1.0 / (t + 1), epsilon=0.1 * (t + 1))
    rl.eval_metrics(3, loss=0.25, eval_loss=0.3, eval_acc=0.9)
    rl.epsilon(3, eps_composed=0.8, eps_round=0.4, rounds=4,
               delta_composed=1e-5)
    rl.warn("w1")
    rl.close("ok")
    s = report_lib.summarize_run(rl.dir)
    assert s["event_counts"]["round"] == 4
    assert s["telemetry"]["loss"]["max"] == 1.0
    assert s["epsilon"]["eps_composed"] == 0.8
    assert len(s["warnings"]) == 1

    out_json = tmp_path / "summary.json"
    rc = report_lib.main([str(tmp_path / "runs"), "--json", str(out_json)])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "eps/round" in printed and "status=ok" in printed
    assert json.loads(out_json.read_text())["epsilon"]["rounds"] == 4
    assert report_lib.main([str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------------------------
# end-to-end acceptance: train quickstart -> runlog -> eps consistency
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_train_quickstart_runlog_epsilon_consistency(tmp_path):
    """README quickstart contract: a runlog-enabled dynamic train run emits
    per-round telemetry whose ε column reproduces the end-of-run
    epsilon_report (the host-side Thm 4.1 accounting), and the composed
    budget in the epsilon events matches composing the JSONL ε trajectory."""
    from repro.launch import train
    res = train.main([
        "--steps", "24", "--workers", "6", "--eval-every", "12",
        "--channel-model", "dynamic", "--scenario", "iot_dense",
        "--runlog-dir", str(tmp_path), "--eps-budget", "5.0",
    ])
    assert res.runner is not None
    assert all(n == 1 for n in res.runner.trace_counts().values())
    runs = report_lib.find_runs(tmp_path)
    assert len(runs) == 1
    man = obs.RunLog.read_manifest(runs[0])
    assert man["status"] == "ok" and man["kind"] == "train"
    assert man["telemetry"] == list(obs.TelemetrySpec().fields)

    rounds = obs.RunLog.read_events(runs[0], "round")
    assert len(rounds) == 25                 # steps + 1, per-round rows
    eps_col = np.asarray([r["epsilon"] for r in rounds])
    rep = obs.RunLog.read_events(runs[0], "epsilon_report")[-1]
    np.testing.assert_allclose(eps_col.max(), rep["eps_worst_round"],
                               rtol=1e-5)
    np.testing.assert_allclose(eps_col.mean(), rep["eps_mean_round"],
                               rtol=1e-5)
    # composed budget from the carry moments == composing the JSONL column
    eps_events = obs.RunLog.read_events(runs[0], "epsilon")
    assert eps_events
    e_ref, _d = privacy.compose_heterogeneous(eps_col.astype(np.float64),
                                              1e-5)
    np.testing.assert_allclose(eps_events[-1]["eps_composed"], e_ref,
                               rtol=1e-3)
    np.testing.assert_allclose(rep["eps_composed"], e_ref, rtol=1e-3)
    # the scan compiled its chunk lengths once each, no retrace warnings
    assert not obs.RunLog.read_events(runs[0], "warning") or all(
        "retrace" not in w["message"]
        for w in obs.RunLog.read_events(runs[0], "warning"))
    # report renders it
    s = report_lib.summarize_run(runs[0])
    assert s["telemetry"]["epsilon"]["n"] == 25
    assert math.isfinite(s["telemetry"]["consensus"]["last"])


# ---------------------------------------------------------------------------
# host spans and device scopes (obs.spans; jax.named_scope in the round)
# ---------------------------------------------------------------------------


def test_span_nests_counts_and_keeps_max_and_total():
    obs.spans.reset()
    with obs.span("outer") as outer:
        for _ in range(3):
            with obs.span("inner", rounds=5) as inner:
                pass
            assert inner.seconds is not None and inner.seconds >= 0.0
    got = obs.spans.counters()
    assert set(got) == {"outer", "inner"}
    assert got["outer"]["count"] == 1 and got["inner"]["count"] == 3
    assert got["outer"]["total_s"] == pytest.approx(outer.seconds)
    # nested: the inner spans lie within the outer one
    assert got["inner"]["total_s"] <= got["outer"]["total_s"]
    assert got["inner"]["max_s"] <= got["inner"]["total_s"]
    assert got["inner"]["max_s"] >= got["inner"]["total_s"] / 3
    # counters() hands out a copy; reset() clears
    got["outer"]["count"] = 99
    assert obs.spans.counters()["outer"]["count"] == 1
    obs.spans.reset()
    assert obs.spans.counters() == {}


def test_span_counts_with_no_profile_running():
    obs.spans.reset()
    with pytest.raises(RuntimeError):
        with obs.span("failing"):
            raise RuntimeError("the span still closes")
    with obs.span("quiet", chunk=1):
        pass
    got = obs.spans.counters()
    assert got["failing"]["count"] == 1 and got["quiet"]["count"] == 1
    obs.spans.reset()


def test_span_is_written_into_a_running_profile(tmp_path):
    """Under a profile the span is a host event of that name, carrying
    its attributes, on the trace's own clock."""
    from jax.profiler import ProfileData
    obs.spans.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("dwfl.chunk.dispatch", rounds=5, chunk=7):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = [e for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for e in line.events
              if e.name == "dwfl.chunk.dispatch"]
    assert len(events) == 1
    assert dict(events[0].stats) == {"rounds": 5, "chunk": 7}
    assert obs.spans.counters()["dwfl.chunk.dispatch"]["count"] == 1
    obs.spans.reset()


def _flat_chunk_runner():
    """A ChunkRunner over the flat static round of a tiny MLP, telemetry
    on, with its first carry."""
    from repro.configs.registry import get_arch
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.core import trajectory as TJ
    from repro.data.device import ClassificationStore
    import repro.models.mlp as mlp
    W, dim = 4, 12
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                             sigma_m=0.5)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(80, dim)).astype(np.float32)
    y = rng.integers(0, 10, 80).astype(np.int32)
    store = ClassificationStore.build(
        x, y, [np.arange(w, 80, W) for w in range(W)], 4)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=dim)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
    _unravel, unravel_row = X.worker_unravelers(wp)
    body = TJ.make_round_body(cfg, proto, store, unravel_row=unravel_row,
                              telemetry=obs.TelemetrySpec())
    carry = TJ.TrajCarry(jax.random.PRNGKey(1), X.flatten_worker_tree(wp),
                         None, obs.init_eps_moments())
    return TJ.ChunkRunner(body, donate=False), carry


def test_chunk_runner_compile_seconds_is_the_compile_span():
    runner, carry = _flat_chunk_runner()
    obs.spans.reset()
    carry, _ = runner.run(carry, 3)
    for _ in range(2):
        carry, _ = runner.run(carry, 3)
    got = obs.spans.counters()
    assert got["dwfl.chunk.compile"]["count"] == 1
    assert runner.compile_seconds[3] == got["dwfl.chunk.compile"]["total_s"]
    assert got["dwfl.chunk.dispatch"]["count"] == 2
    assert runner.trace_counts() == {3: 1}
    obs.spans.reset()


def test_flat_chunk_program_carries_the_round_scopes():
    """The compiled static flat chunk program names the gradient pass, the
    mix and the telemetry in its ops' op_name metadata, which is what a
    device trace attributes each op by."""
    import re
    runner, carry = _flat_chunk_runner()
    carry, _ = runner.run(carry, 2)
    text = runner.compiled_text(carry, 2)
    names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("dwfl.grad", "dwfl.mix", "dwfl.telemetry"):
        assert scope + "/" in names, scope
    assert runner.trace_counts() == {2: 1}


def test_train_main_spans_its_set_up():
    """train.main opens dwfl.main around everything it does and
    dwfl.main.data around the data it builds, and its one chunk compile
    lies inside both counts."""
    from repro.launch import train
    obs.spans.reset()
    res = train.main(["--steps", "1", "--workers", "3", "--hidden", "8",
                      "--dataset-size", "240", "--batch-size", "4",
                      "--flat-buffer", "--eval-every", "0",
                      "--chunk-rounds", "2"])
    got = obs.spans.counters()
    assert got["dwfl.main"]["count"] == 1
    assert got["dwfl.main.data"]["count"] == 1
    assert got["dwfl.chunk.compile"]["count"] == 1
    assert got["dwfl.main.data"]["total_s"] < got["dwfl.main"]["total_s"]
    assert got["dwfl.chunk.compile"]["total_s"] < got["dwfl.main"]["total_s"]
    assert res.runner.compile_seconds == {
        2: got["dwfl.chunk.compile"]["total_s"]}
    obs.spans.reset()
