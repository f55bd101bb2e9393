"""repro.fleet — batched multi-replicate simulation (ISSUE 2 tentpole).

The load-bearing guarantees:
  * batched-vs-loop equivalence: the vmapped fleet round produces, per
    replicate, exactly what the single-network pipeline produces for the
    same per-replicate key (same seeds ⇒ identical trajectories),
  * batched ε-accounting equals per-replicate epsilon_trajectory /
    compose_heterogeneous,
  * zero retraces across replicate batches,
  * the optional shard_map path computes the vmapped result.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange as X
from repro.core import privacy
from repro.core import protocol as P
from repro.fleet import (FleetEngine, ScenarioGrid, fleet_epsilon_report,
                         mean_ci, run_grid, stack_rounds)

R, N = 3, 5


def _proto(**kw):
    base = dict(scheme="dwfl", n_workers=N, gamma=0.05, eta=0.4, clip=1.0,
                p_dbm=60.0, channel_model="dynamic", scenario="iot_dense",
                replicates=R)
    base.update(kw)
    return P.ProtocolConfig(**base)


def _tiny_model(n_workers=N, reps=R, input_dim=12, batch=4):
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    key = jax.random.PRNGKey(0)
    params = mlp.init(key, cfg, input_dim=input_dim)
    wp1 = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_workers,) + a.shape), params)
    batch1 = {"x": jax.random.normal(key, (n_workers, batch, input_dim)),
              "y": jnp.zeros((n_workers, batch), jnp.int32)}
    stack = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape), t)
    return cfg, wp1, batch1, stack(wp1), stack(batch1)


def test_fleet_requires_dynamic():
    with pytest.raises(ValueError):
        FleetEngine(P.ProtocolConfig(scheme="dwfl", n_workers=N,
                                     channel_model="static"))


def test_fleet_shapes():
    fleet = FleetEngine(_proto())
    states = fleet.init(jax.random.PRNGKey(0))
    for leaf in jax.tree_util.tree_leaves(states):
        assert leaf.shape[0] == R
    states, chans, masks, Ws = fleet.round(jax.random.PRNGKey(1), states)
    assert chans.h.shape == (R, N) and chans.c.shape == (R,)
    assert masks.shape == (R, N) and Ws.shape == (R, N, N)
    chans, masks, Ws = fleet.trajectory(jax.random.PRNGKey(2), 4)
    assert chans.h.shape == (R, 4, N) and Ws.shape == (R, 4, N, N)


def test_fleet_round_equals_python_loop():
    """Same per-replicate keys ⇒ the batched round IS the per-network round,
    replicate by replicate (channel level, multi-round)."""
    proto = _proto()
    fleet = FleetEngine(proto)
    sim = fleet.sim
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(3), 3)

    states = fleet.init(k0)
    init_keys = fleet.split_keys(k0)
    loop_states = [sim.init(k) for k in init_keys]
    for r in range(R):
        for a, b in zip(jax.tree_util.tree_leaves(states),
                        jax.tree_util.tree_leaves(loop_states[r])):
            np.testing.assert_allclose(np.asarray(a[r]), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    for kk in (k1, k2):  # two rounds, threading state through both paths
        states, chans, masks, Ws = fleet.round(kk, states)
        round_keys = fleet.split_keys(kk)
        for r in range(R):
            ls, ch, mask, Wm = sim.round(round_keys[r], loop_states[r])
            loop_states[r] = ls
            np.testing.assert_allclose(np.asarray(chans.h[r]),
                                       np.asarray(ch.h), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(chans.c[r]),
                                       np.asarray(ch.c), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(Ws[r]), np.asarray(Wm),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(masks[r]),
                                          np.asarray(mask))


def test_fleet_step_equals_python_loop():
    """The vmapped train step reproduces the single-replicate dynamic step
    for each replicate's (key, channel, mixing matrix)."""
    proto = _proto()
    fleet = FleetEngine(proto)
    cfg, wp1, batch1, wpR, batchR = _tiny_model()
    states = fleet.init(jax.random.PRNGKey(4))
    _, chans, _, Ws = fleet.round(jax.random.PRNGKey(5), states)
    keys = fleet.split_keys(jax.random.PRNGKey(6))
    spec1, specR = X.make_flat_spec(wp1), X.make_flat_spec(wpR, lead_axes=2)

    fleet_step = jax.jit(fleet.make_fleet_step(cfg, spec=specR))
    flat_f, metrics_f = fleet_step(specR.flatten(wpR), batchR, keys, chans,
                                   Ws)

    base_step = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                       spec1.unravel_row))
    for r in range(R):
        chan_r = jax.tree_util.tree_map(lambda a: a[r], chans)
        flat_r, metrics_r = base_step(spec1.flatten(wp1), batch1, keys[r],
                                      chan_r, Ws[r])
        np.testing.assert_allclose(np.asarray(flat_f[r]), np.asarray(flat_r),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(float(metrics_f["loss"][r]),
                                   float(metrics_r["loss"]), rtol=1e-5)


def test_batched_epsilon_matches_per_replicate():
    """[R, T, N] batched accounting == stacked per-replicate trajectories;
    batched composition == row-wise compose_heterogeneous."""
    proto = _proto(target_epsilon=0.0, sigma=0.8)
    fleet = FleetEngine(proto)
    chans, _masks, Ws = fleet.trajectory(jax.random.PRNGKey(7), 6)

    batched = np.asarray(privacy.epsilon_trajectory_batched(
        proto.gamma, proto.clip, chans, proto.delta, Ws))
    assert batched.shape == (R, 6, N)
    for r in range(R):
        chan_r = jax.tree_util.tree_map(lambda a: a[r], chans)
        per = np.asarray(privacy.epsilon_trajectory(
            proto.gamma, proto.clip, chan_r, proto.delta, Ws[r]))
        np.testing.assert_allclose(batched[r], per, rtol=1e-6, atol=1e-7)

    per_round = batched.max(axis=2)                      # [R, T]
    eps_b, delta_b = privacy.compose_heterogeneous_batched(
        per_round, proto.delta)
    assert eps_b.shape == (R,)
    for r in range(R):
        e, d = privacy.compose_heterogeneous(per_round[r], proto.delta)
        np.testing.assert_allclose(eps_b[r], e, rtol=1e-12)
        np.testing.assert_allclose(delta_b[r], d, rtol=1e-12)

    rep = fleet_epsilon_report(proto, chans, Ws)
    np.testing.assert_allclose(rep["epsilon_composed_per_replicate"], eps_b,
                               rtol=1e-12)
    m, ci = mean_ci(eps_b)
    assert rep["epsilon_composed_mean"] == pytest.approx(m)
    assert rep["epsilon_composed_ci95"] == pytest.approx(ci)


def test_fleet_zero_retrace_across_replicate_batches():
    """One compiled fleet round serves every fresh stacked realization."""
    proto = _proto()
    fleet = FleetEngine(proto)
    cfg, _wp1, _batch1, wpR, batchR = _tiny_model()
    traces = {"n": 0}
    specR = X.make_flat_spec(wpR, lead_axes=2)
    _round = fleet.make_fleet_round(cfg, spec=specR)

    def counted(k, states, wp, batch):
        traces["n"] += 1
        return _round(k, states, wp, batch)

    fleet_round = jax.jit(counted)
    states = fleet.init(jax.random.PRNGKey(8))
    wp = specR.flatten(wpR)
    for t in range(4):
        states, wp, _m, _c, _w = fleet_round(
            jax.random.fold_in(jax.random.PRNGKey(9), t), states, wp, batchR)
    assert traces["n"] == 1


def test_fleet_power_axis():
    """Per-replicate transmit power (the scenario-variant axis): higher P
    ⇒ larger alignment constant c, same fading state."""
    proto = _proto()
    sim = proto.simulator()
    state = sim.init(jax.random.PRNGKey(10))
    from repro.core.channel import dbm_to_watts
    Ps = jnp.asarray(dbm_to_watts(np.array([50.0, 60.0, 70.0])), jnp.float32)
    _, chans, _, _ = jax.vmap(
        lambda p: sim.round(jax.random.PRNGKey(11), state, P=p))(Ps)
    c = np.asarray(chans.c)
    assert c[0] < c[1] < c[2]

    # engine-level: a uniform power_dbm override equals the default path
    f_default = FleetEngine(proto)
    f_override = FleetEngine(proto, power_dbm=[proto.p_dbm] * R)
    s0 = f_default.init(jax.random.PRNGKey(12))
    _, ch_a, _, _ = f_default.round(jax.random.PRNGKey(13), s0)
    _, ch_b, _, _ = f_override.round(jax.random.PRNGKey(13), s0)
    np.testing.assert_allclose(np.asarray(ch_a.h), np.asarray(ch_b.h),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ch_a.c), np.asarray(ch_b.c),
                               rtol=1e-6)


def test_fleet_sharded_matches_vmapped():
    """The shard_map path (1-device mesh on CPU) computes exactly the
    vmapped result."""
    try:
        from repro.launch.mesh import _make_mesh
        mesh = _make_mesh((1,), ("replicas",))
    except Exception as e:  # pragma: no cover
        pytest.skip(f"mesh unavailable: {e}")
    proto = _proto()
    fleet = FleetEngine(proto)
    cfg, _wp1, _batch1, wpR, batchR = _tiny_model()
    states = fleet.init(jax.random.PRNGKey(14))
    _, chans, _, Ws = fleet.round(jax.random.PRNGKey(15), states)
    keys = fleet.split_keys(jax.random.PRNGKey(16))

    specR = X.make_flat_spec(wpR, lead_axes=2)
    flat = specR.flatten(wpR)
    plain = jax.jit(fleet.make_fleet_step(cfg, spec=specR))
    sharded = jax.jit(fleet.make_fleet_step(cfg, mesh=mesh, spec=specR))
    flat_a, m_a = plain(flat, batchR, keys, chans, Ws)
    flat_b, m_b = sharded(flat, batchR, keys, chans, Ws)
    np.testing.assert_allclose(np.asarray(flat_a), np.asarray(flat_b),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_a["loss"]),
                               np.asarray(m_b["loss"]), rtol=1e-5)


def test_fleet_sharded_rejects_indivisible():
    proto = _proto()
    fleet = FleetEngine(proto)        # R = 3
    from repro.launch.mesh import _make_mesh

    class FakeMesh:
        axis_names = ("replicas",)
        devices = np.empty((2,), object)

    with pytest.raises(ValueError, match="divisible"):
        fleet.make_fleet_step(None, mesh=FakeMesh(),
                              unravel_row=lambda v: v)


def test_stack_rounds_layout():
    """stack_rounds stacks per-round [R, ...] pytrees along axis 1 —
    replicate-major [R, T, ...], matching FleetEngine.trajectory."""
    proto = _proto()
    fleet = FleetEngine(proto)
    states = fleet.init(jax.random.PRNGKey(17))
    log = []
    for t in range(3):
        states, chans, _m, _w = fleet.round(
            jax.random.fold_in(jax.random.PRNGKey(18), t), states)
        log.append(chans)
    stacked = stack_rounds(log)
    assert stacked.h.shape == (R, 3, N)
    np.testing.assert_allclose(np.asarray(stacked.h[:, 1]),
                               np.asarray(log[1].h), rtol=0)


def test_scenario_grid_runs(tmp_path):
    grid = ScenarioGrid(scenarios=("static_paper",), n_workers=(4,),
                        p_dbm=(60.0,), target_epsilon=(1.0,),
                        replicates=2, steps=2)
    path = str(tmp_path / "sweep.json")
    out = run_grid(grid, json_path=path)
    assert len(out["rows"]) == grid.size() == 1
    row = out["rows"][0]
    for field in ("loss_mean", "loss_ci95", "acc_mean", "acc_ci95",
                  "epsilon_composed_mean", "epsilon_composed_ci95",
                  "us_per_round"):
        assert np.isfinite(row[field]), field
    import json
    with open(path) as f:
        assert json.load(f)["rows"][0]["scenario"] == "static_paper"


def test_fleet_flat_buffer_round():
    """ISSUE 3: the flat-buffer fleet path — [R, W, d] persistent buffer,
    vmapped fused dp_mix round — runs, keeps the replicate axis intact,
    and its unraveled params keep the model's structure."""
    proto = _proto()
    fleet = FleetEngine(proto)
    cfg, wp1, batch1, wpR, batchR = _tiny_model()
    key = jax.random.PRNGKey(5)
    # engine-built buffer (default model dims): [R, W, d] f32, replicate-
    # independent rows recoverable
    flat0, unravel0, _ = fleet.init_flat_params(key, cfg)
    assert flat0.ndim == 3 and flat0.shape[:2] == (R, N)
    assert flat0.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(unravel0(flat0)):
        assert leaf.shape[:2] == (R, N)
    # the fused round on the test-scale model
    flat = X.flatten_worker_tree(wpR, lead_axes=2)
    unravel, unravel_row = X.worker_unravelers(wpR, lead_axes=2)
    tree = unravel(flat)
    for leaf in jax.tree_util.tree_leaves(tree):
        assert leaf.shape[:2] == (R, N)
    fleet_round = jax.jit(fleet.make_fleet_round(cfg,
                                                 unravel_row=unravel_row))
    states = fleet.init(key)
    states, flat2, metrics, chans, Ws = fleet_round(
        jax.random.PRNGKey(6), states, flat, batchR)
    assert flat2.shape == flat.shape
    assert bool(jnp.isfinite(flat2).all())
    assert metrics["loss"].shape == (R,)
    assert np.isfinite(np.asarray(metrics["loss"])).all()
    # no unraveler (and no spec) is a loud error, not a silent break
    with pytest.raises(ValueError):
        fleet.make_fleet_step(cfg)


def test_mean_ci():
    m, ci = mean_ci([1.0, 1.0, 1.0])
    assert m == 1.0 and ci == 0.0
    m, ci = mean_ci([5.0])
    assert m == 5.0 and ci == 0.0
    v = np.random.default_rng(0).normal(0, 1, 400)
    m, ci = mean_ci(v)
    assert abs(m) < ci  # true mean 0 inside the CI
