"""repro.shard — model-axis sharding of the flat DWFL buffer (ISSUE 5
tentpole).

The load-bearing guarantee mirrors the trajectory engine's: sharding is
INVISIBLE to the computation. The fused dp_mix round is column-separable
and its noise is counter-addressed with a layout-independent stride
(ShardLayout.counter_width), so for ANY shard count the union of the
per-shard streams IS the single-device stream — asserted BITWISE here for
the window primitive, the logical single-device mode, whole scan
trajectories, and (in a subprocess with real host devices) the shard_map
mesh mode of the acceptance criterion. The fleet-flat configuration is
ULP-close for the same reason the scan engine documents (per-program FMA
contraction of the R-vmapped matmul)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange as X
from repro.core import protocol as P
from repro.core import trajectory as TJ
from repro.data.device import ClassificationStore
from repro.shard import (LANES, ShardLayout, dp_mix_round_sharded,
                         make_sharded_dynamic_flat_train_step,
                         make_sharded_flat_train_step, shard_window_round)

W, DIM, BATCH, NDATA = 5, 12, 4, 160


def _cfg():
    from repro.configs.registry import get_arch
    return get_arch("dwfl-paper").replace(d_model=8)


def _proto(**kw):
    base = dict(scheme="dwfl", n_workers=W, gamma=0.05, eta=0.4, clip=1.0,
                p_dbm=60.0, sigma=0.7, sigma_m=0.5)
    base.update(kw)
    return P.ProtocolConfig(**base)


def _wp(cfg):
    import repro.models.mlp as mlp
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=DIM)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)


def _store(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(NDATA, DIM)).astype(np.float32)
    y = rng.integers(0, 10, NDATA).astype(np.int32)
    parts = [np.arange(w, NDATA, W) for w in range(W)]
    return ClassificationStore.build(x, y, parts, BATCH)


def _batch(seed=1):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.normal(size=(W, BATCH, DIM))
                             .astype(np.float32)),
            "y": jnp.asarray(rng.integers(0, 10, (W, BATCH))
                             .astype(np.int32))}


# ---------------------------------------------------------------------------
# layout geometry
# ---------------------------------------------------------------------------


def test_layout_geometry_and_kernel_contract():
    from repro.kernels.dp_mix import dp_mix as K
    assert LANES == K.LANES           # layout.py mirrors the kernel tile
    lay = ShardLayout(500, 4)
    assert lay.counter_width == 512   # roundup(d, LANES), layout-free
    assert ShardLayout(500, 1).counter_width == 512
    assert lay.shard_width == 128 and lay.padded_width == 512
    np.testing.assert_array_equal(lay.col_offsets(), [0, 128, 256, 384])
    # pad/unpad/relayout roundtrips
    flat = jnp.arange(2 * 500, dtype=jnp.float32).reshape(2, 500)
    padded = lay.pad(flat)
    assert padded.shape == (2, 512)
    np.testing.assert_array_equal(np.asarray(lay.unpad(padded)),
                                  np.asarray(flat))
    other = ShardLayout(500, 2)
    re = lay.relayout(padded, other)
    assert re.shape == (2, other.padded_width)
    np.testing.assert_array_equal(np.asarray(other.unpad(re)),
                                  np.asarray(flat))
    with pytest.raises(ValueError):
        lay.relayout(padded, ShardLayout(400, 2))
    # metadata roundtrip + drift guard
    assert ShardLayout.from_meta(lay.to_meta()) == lay
    bad = dict(lay.to_meta(), shard_width=64)
    with pytest.raises(ValueError):
        ShardLayout.from_meta(bad)


def test_flat_spec_layout_awareness():
    cfg = _cfg()
    wp = _wp(cfg)
    spec0 = X.make_flat_spec(wp)
    spec2 = X.make_flat_spec(wp, n_shards=2)
    assert spec0.layout is None and spec0.width == spec0.d
    assert spec2.n_shards == 2 and spec2.width == spec2.layout.padded_width
    f0, f2 = spec0.flatten(wp), spec2.flatten(wp)
    assert f2.shape[-1] == spec2.width
    np.testing.assert_array_equal(np.asarray(spec2.unpad(f2)),
                                  np.asarray(f0))
    assert np.all(np.asarray(f2)[..., spec2.d:] == 0.0)
    # both layouts unravel to the identical tree
    for a, b in zip(jax.tree_util.tree_leaves(spec0.unravel(f0)),
                    jax.tree_util.tree_leaves(spec2.unravel(f2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        X.FlatSpec(wp, 1, ShardLayout(spec0.d + 1, 2))


# ---------------------------------------------------------------------------
# the window primitive: per-shard streams tile the single-device stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_sharded_round_bitwise_reconstructs_noise_stream(n_shards):
    from repro.core.channel import ChannelConfig
    from repro.kernels.dp_mix import ops as mix_ops
    N, d = 6, 500
    chan = ChannelConfig(n_workers=N, p_dbm=30.0, sigma=0.7, sigma_m=0.3,
                         seed=3).realize()
    plan = X.plan_complete(None, chan)
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (N, d))
    g = jax.random.normal(jax.random.fold_in(key, 1), (N, d)) * 0.2
    full = mix_ops.dp_mix_round_plan(p, g, 7, plan, gamma=0.05, eta=0.4)
    lay = ShardLayout(d, n_shards)
    out = dp_mix_round_sharded(lay.pad(p), lay.pad(g), jnp.int32(7), plan,
                               lay, gamma=0.05, eta=0.4)
    np.testing.assert_array_equal(np.asarray(lay.unpad(out)),
                                  np.asarray(full))
    assert np.all(np.asarray(out)[:, d:] == 0.0)   # padding invariant
    # per-window calls reconstruct the same columns individually
    s = 1 % n_shards
    win = shard_window_round(
        lay.pad(p)[:, s * lay.shard_width:(s + 1) * lay.shard_width],
        lay.pad(g)[:, s * lay.shard_width:(s + 1) * lay.shard_width],
        jnp.int32(7), plan, jnp.int32(s * lay.shard_width), lay,
        gamma=0.05, eta=0.4)
    np.testing.assert_array_equal(
        np.asarray(win),
        np.asarray(out)[:, s * lay.shard_width:(s + 1) * lay.shard_width])


def test_sharded_round_noiseless_gossip_path():
    """noisy=False (gossip) skips the PRNG entirely; sharding must still
    mask padding and match the unsharded mixing bitwise."""
    from repro.core.channel import ChannelConfig
    from repro.kernels.dp_mix import ops as mix_ops
    N, d = 6, 300
    chan = ChannelConfig(n_workers=N, p_dbm=30.0, sigma=0.0, sigma_m=0.0,
                         seed=3).realize()
    plan = X.plan_gossip(None, chan)
    key = jax.random.PRNGKey(2)
    p = jax.random.normal(key, (N, d))
    g = jnp.zeros_like(p)
    full = mix_ops.dp_mix_round_plan(p, g, 7, plan, gamma=0.0, eta=0.5)
    lay = ShardLayout(d, 2)
    out = dp_mix_round_sharded(lay.pad(p), lay.pad(g), jnp.int32(7), plan,
                               lay, gamma=0.0, eta=0.5)
    np.testing.assert_array_equal(np.asarray(lay.unpad(out)),
                                  np.asarray(full))
    assert np.all(np.asarray(out)[:, d:] == 0.0)


# ---------------------------------------------------------------------------
# sharded train steps (logical single-device mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
def test_logical_sharded_static_step_bitwise(n_shards):
    cfg = _cfg()
    proto = _proto()
    wp = _wp(cfg)
    spec0 = X.make_flat_spec(wp)
    base = jax.jit(P.make_flat_train_step(cfg, proto, spec0.unravel_row))
    f1, m1 = base(spec0.flatten(wp), _batch(), jax.random.PRNGKey(42))
    spec = X.make_flat_spec(wp, n_shards=n_shards)
    step = jax.jit(make_sharded_flat_train_step(cfg, proto, spec))
    f2, m2 = step(spec.flatten(wp), _batch(), jax.random.PRNGKey(42))
    np.testing.assert_array_equal(np.asarray(spec.unpad(f2)),
                                  np.asarray(f1))
    for k in m1:
        np.testing.assert_array_equal(np.asarray(m1[k]), np.asarray(m2[k]),
                                      err_msg=k)


def test_logical_sharded_dynamic_step_bitwise():
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense")
    sim = proto.simulator()
    wp = _wp(cfg)
    net0 = sim.init(jax.random.PRNGKey(1))
    _, chan, _, Wm = jax.jit(sim.round)(jax.random.PRNGKey(2), net0)
    spec0 = X.make_flat_spec(wp)
    base = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                  spec0.unravel_row))
    f1, _ = base(spec0.flatten(wp), _batch(), jax.random.PRNGKey(3), chan,
                 Wm)
    spec = X.make_flat_spec(wp, n_shards=2)
    step = jax.jit(make_sharded_dynamic_flat_train_step(cfg, proto, spec))
    f2, _ = step(spec.flatten(wp), _batch(), jax.random.PRNGKey(3), chan,
                 Wm)
    np.testing.assert_array_equal(np.asarray(spec.unpad(f2)),
                                  np.asarray(f1))


def test_fleet_logical_sharded_step_ulp_close():
    """[R, W, width] buffer, logical model shards inside the vmapped
    replicate round: ULP-close to the plain fleet-flat step (the same
    FMA-contraction caveat as the scan engine, DESIGN.md §10); the
    replicate axis stays intact."""
    from repro.fleet import FleetEngine
    R = 2
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense",
                   replicates=R)
    fleet = FleetEngine(proto)
    # engine-built spec carries the 2 lead axes and the layout
    _f, _s = fleet.init_flat_spec(jax.random.PRNGKey(4), cfg, n_shards=2)
    assert _s.lead_axes == 2 and _s.n_shards == 2
    assert _f.shape == (R, W, _s.width)
    # the test-scale model (DIM-dim inputs) for the actual parity run
    wpR = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), _wp(cfg))
    spec0 = X.make_flat_spec(wpR, lead_axes=2)
    spec2 = X.make_flat_spec(wpR, lead_axes=2, n_shards=2)
    flat0, flat2 = spec0.flatten(wpR), spec2.flatten(wpR)
    states = fleet.init(jax.random.PRNGKey(5))
    _, chans, _, Ws = fleet.round(jax.random.PRNGKey(6), states)
    keys = fleet.split_keys(jax.random.PRNGKey(7))
    batch = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), _batch())
    plain = jax.jit(fleet.make_fleet_step(cfg, spec=spec0))
    sharded = jax.jit(fleet.make_fleet_step(cfg, spec=spec2))
    f_a, m_a = plain(flat0, batch, keys, chans, Ws)
    f_b, m_b = sharded(flat2, batch, keys, chans, Ws)
    assert f_b.shape == (R, W, spec2.width)
    np.testing.assert_allclose(np.asarray(spec2.unpad(f_b)),
                               np.asarray(f_a), rtol=5e-6, atol=5e-7)
    np.testing.assert_allclose(np.asarray(m_a["loss"]),
                               np.asarray(m_b["loss"]), rtol=1e-6)


def test_trajectory_sharded_scan_bitwise_and_chunk_invariant():
    """The scan engine with a sharded carry: K-chunked sharded
    trajectories equal the unsharded per-round loop bitwise on the
    canonical columns — sharding composes with chunking without touching
    the PRNG stream."""
    cfg = _cfg()
    proto = _proto()
    wp = _wp(cfg)
    store = _store()
    spec0 = X.make_flat_spec(wp)
    body0 = TJ.make_round_body(cfg, proto, store, spec=spec0)
    c0 = TJ.TrajCarry(jax.random.PRNGKey(3), spec0.flatten(wp))
    ref, out_ref = TJ.run_per_round(body0, c0, 6)

    spec = X.make_flat_spec(wp, n_shards=2)
    body = TJ.make_round_body(cfg, proto, store, spec=spec)
    c1 = TJ.TrajCarry(jax.random.PRNGKey(3), spec.flatten(wp))
    runner = TJ.ChunkRunner(body, donate=False)
    outs = []
    for k in (4, 2):
        c1, out = runner.run(c1, k)
        outs.append(out)
    out_scan = TJ.concat_chunks(outs)
    np.testing.assert_array_equal(np.asarray(spec.unpad(c1.params)),
                                  np.asarray(ref.params))
    np.testing.assert_array_equal(np.asarray(c1.key), np.asarray(ref.key))
    for k in ("loss", "grad_norm", "param_norm"):
        np.testing.assert_array_equal(np.asarray(out_ref["metrics"][k]),
                                      np.asarray(out_scan["metrics"][k]),
                                      err_msg=k)


def test_sharded_step_requires_layout_and_matching_mesh():
    cfg = _cfg()
    proto = _proto()
    wp = _wp(cfg)
    spec0 = X.make_flat_spec(wp)           # no layout
    with pytest.raises(ValueError):
        make_sharded_flat_train_step(cfg, proto, spec0)
    spec = X.make_flat_spec(wp, n_shards=2)
    from repro.launch.mesh import _make_mesh
    mesh1 = _make_mesh((1,), ("model",))   # 1 device != 2 shards
    with pytest.raises(ValueError):
        make_sharded_flat_train_step(cfg, proto, spec, mesh=mesh1)
    mesh_r = _make_mesh((1,), ("replicas",))
    with pytest.raises(ValueError):
        make_sharded_flat_train_step(cfg, proto, spec, mesh=mesh_r)


# ---------------------------------------------------------------------------
# the acceptance criterion: real host-device mesh, model=2 — subprocess
# (tests run single-device; forcing the device count needs a fresh process)
# ---------------------------------------------------------------------------


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.launch import mesh as mesh_lib
    from repro.launch.shardings import flat_buffer_sharding
    from jax.sharding import PartitionSpec as PS
    from repro.shard import (make_sharded_flat_train_step,
                             make_sharded_dynamic_flat_train_step)
    from repro.shard.round import worker_split_grads
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp

    W, DIM, BATCH = 5, 12, 4
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                             sigma_m=0.5)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=DIM)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(W, BATCH, DIM))
                              .astype(np.float32)),
             "y": jnp.asarray(rng.integers(0, 10, (W, BATCH))
                              .astype(np.int32))}
    spec0 = X.make_flat_spec(wp)
    flat0 = spec0.flatten(wp)
    base = jax.jit(P.make_flat_train_step(cfg, proto, spec0.unravel_row))
    f1, m1 = base(flat0, batch, jax.random.PRNGKey(42))

    # static round on the model=2 mesh, noise stream included. The mesh
    # mode differentiates and clips each worker on its leaves
    # (shard.round.worker_split_grads): the clip norm sums per-leaf sums
    # of squares where the single-device round sums the row once, so the
    # two agree to float32 rounding, not bitwise. Measured here: per-
    # worker norms 1 ULP apart on 2 of 5 workers (9e-8 relative), the
    # buffer equal; rtol=1e-6 is the dynamic mesh round's tolerance.
    mesh = mesh_lib.make_shard_mesh(2)
    spec = X.make_flat_spec(wp, n_shards=2)
    flat = jax.device_put(spec.flatten(wp),
                          flat_buffer_sharding(spec, mesh))
    step = jax.jit(make_sharded_flat_train_step(cfg, proto, spec,
                                                mesh=mesh))
    f2, m2 = step(flat, batch, jax.random.PRNGKey(42))
    np.testing.assert_allclose(np.asarray(spec.unpad(f2)), np.asarray(f1),
                               rtol=1e-6,
                               err_msg="static mesh round != single-device "
                                       "round")
    # per-worker losses, gradients and pre-clip norms of the mesh pass
    grads = jax.jit(jax.shard_map(
        worker_split_grads(cfg, proto, spec, "model"), mesh=mesh,
        in_specs=(PS(None, "model"), PS()),
        out_specs=(PS(), PS(None, "model"), PS()), check_vma=False))
    lm, gm, nm = grads(flat, batch)
    l0, g0, n0 = jax.jit(P._make_flat_local_pass(cfg, proto,
                                                 spec0.unravel_row))(
        flat0, batch)
    np.testing.assert_allclose(np.asarray(lm), np.asarray(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(n0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(spec.unpad(gm)), np.asarray(g0),
                               rtol=1e-6)
    # metric MEANS are ULP-level only: XLA picks the final mean's
    # reduction strategy per program (param_norm additionally associates
    # psum partials differently).
    for k in ("loss", "grad_norm", "param_norm"):
        np.testing.assert_allclose(np.asarray(m1[k]), np.asarray(m2[k]),
                                   rtol=1e-6)

    # chunk-budget invariance: the chunk plan is pure data movement, so
    # EVERY max_chunk_cols realizes the bitwise-identical mesh round (and
    # the same metrics — identical per-leaf values, identical reduce
    # shapes)
    for cap in (64, 257):
        spec_b = X.make_flat_spec(wp, n_shards=2, max_chunk_cols=cap)
        assert len(spec_b.chunk_plan.exec_segments()) > 1 or cap >= \\
            spec_b.layout.shard_width
        step_b = jax.jit(make_sharded_flat_train_step(cfg, proto, spec_b,
                                                      mesh=mesh))
        fb, mb = step_b(flat, batch, jax.random.PRNGKey(42))
        assert np.array_equal(np.asarray(spec_b.unpad(fb)),
                              np.asarray(spec.unpad(f2))), \\
            f"max_chunk_cols={cap} changed the sharded round"
        for k in m2:
            assert np.array_equal(np.asarray(mb[k]), np.asarray(m2[k])), k

    # dynamic round, same criterion
    proto_d = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                               eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                               sigma_m=0.5, channel_model="dynamic",
                               scenario="iot_dense")
    sim = proto_d.simulator()
    net0 = sim.init(jax.random.PRNGKey(1))
    _, chan, _, Wm = jax.jit(sim.round)(jax.random.PRNGKey(2), net0)
    base_d = jax.jit(P.make_dynamic_flat_train_step(cfg, proto_d,
                                                    spec0.unravel_row))
    fd1, _ = base_d(flat0, batch, jax.random.PRNGKey(43), chan, Wm)
    step_d = jax.jit(make_sharded_dynamic_flat_train_step(
        cfg, proto_d, spec, mesh=mesh))
    fd2, _ = step_d(flat, batch, jax.random.PRNGKey(43), chan, Wm)
    # the dynamic noise fields are bitwise: the same round sharded
    # logically (one program, same counters) equals the single-device
    # round exactly. On the mesh, XLA contracts the traced-W mix chain
    # into FMAs differently per device program, so the buffer agrees to
    # the mesh-mean ULP tolerance (1 ULP in a handful of entries on CPU).
    step_l = jax.jit(make_sharded_dynamic_flat_train_step(
        cfg, proto_d, spec, mesh=None))
    fd3, _ = step_l(spec.flatten(wp), batch, jax.random.PRNGKey(43), chan,
                    Wm)
    assert np.array_equal(np.asarray(spec.unpad(fd3)), np.asarray(fd1)), \\
        "dynamic logical-shard round != single-device round"
    np.testing.assert_allclose(np.asarray(spec.unpad(fd2)), np.asarray(fd1),
                               rtol=1e-6,
                               err_msg="dynamic mesh round != single-device "
                                       "round")

    # fleet-flat on the 2-D (replicas=2, model=2) mesh: within 2 ULP
    from repro.fleet import FleetEngine
    R = 2
    proto_f = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                               eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                               sigma_m=0.5, channel_model="dynamic",
                               scenario="iot_dense", replicates=R)
    fleet = FleetEngine(proto_f)
    wpR = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), wp)
    spec0f = X.make_flat_spec(wpR, lead_axes=2)
    spec2f = X.make_flat_spec(wpR, lead_axes=2, n_shards=2)
    flat0f = spec0f.flatten(wpR)
    states = fleet.init(jax.random.PRNGKey(5))
    _, chans, _, Ws = fleet.round(jax.random.PRNGKey(6), states)
    keys = fleet.split_keys(jax.random.PRNGKey(7))
    batchR = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), batch)
    mesh2 = mesh_lib.make_shard_mesh(2, n_replicas=2)
    flatm = jax.device_put(
        spec2f.flatten(wpR),
        flat_buffer_sharding(spec2f, mesh2, replicate_axis="replicas"))
    plain = jax.jit(fleet.make_fleet_step(cfg, spec=spec0f))
    shard2d = jax.jit(fleet.make_fleet_step(cfg, mesh=mesh2, spec=spec2f))
    fa, ma = plain(flat0f, batchR, keys, chans, Ws)
    fb, mb = shard2d(flatm, batchR, keys, chans, Ws)
    np.testing.assert_allclose(np.asarray(spec2f.unpad(fb)),
                               np.asarray(fa), rtol=5e-6, atol=5e-7)
    print("MESH_PARITY_OK")
""")


@pytest.mark.slow
def test_mesh_model2_round_parity_subprocess():
    """Acceptance criterion: on a host-device mesh with model=2
    (XLA_FLAGS=--xla_force_host_platform_device_count), the sharded
    round agrees with the single-device round (noise stream included) to
    float32 rounding for the static channel, its per-worker losses,
    gradients and pre-clip norms too, and is bitwise across chunk
    budgets; the dynamic round draws the same noise bitwise and agrees
    to mesh-mean ULPs, and the fleet-flat 2-D-mesh path within 2 ULP."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "MESH_PARITY_OK" in res.stdout


_LEAF_SCRIPT = textwrap.dedent("""
    import os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.launch import mesh as mesh_lib
    from repro.launch.shardings import flat_buffer_sharding
    from repro.shard import make_sharded_flat_train_step
    from repro.shard.round import _piece_map
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp

    cap = None if sys.argv[1] == "none" else int(sys.argv[1])
    # one worker a device, as the LM cell; lane-aligned leaves but the
    # last bias, so the collectives move [.., k, 128] and plain columns
    W, S, DIM, BATCH = 4, 4, 128, 4
    cfg = get_arch("dwfl-paper").replace(d_model=128)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=0.05, p_dbm=60.0, sigma=0.7,
                             sigma_m=0.5)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=DIM)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
    rng = np.random.default_rng(1)
    batch = {"x": jnp.asarray(rng.normal(size=(W, BATCH, DIM))
                              .astype(np.float32)),
             "y": jnp.asarray(rng.integers(0, 10, (W, BATCH))
                              .astype(np.int32))}
    key = jax.random.PRNGKey(42)
    mesh = mesh_lib.make_shard_mesh(S)

    def mesh_round(c):
        spec = X.make_flat_spec(wp, n_shards=S, max_chunk_cols=c)
        flat = jax.device_put(spec.flatten(wp),
                              flat_buffer_sharding(spec, mesh))
        step = jax.jit(make_sharded_flat_train_step(cfg, proto, spec,
                                                    mesh=mesh))
        return spec, step, flat

    spec, step, flat = mesh_round(cap)
    segs = spec.chunk_plan.exec_segments()
    lane = _piece_map(spec, segs)[1]
    if cap != 64:
        assert any(lane) and not all(lane), lane
    # the worker row is gone: no array of the compiled step has d or the
    # padded width on its last axis (no leaf's shape has either)
    wide = {spec.d, spec.width}
    assert not wide & {n for sh in spec.leaf_shapes() for n in sh}
    text = step.lower(flat, batch, key).compile().as_text()
    rows = [m.group(0) for m in re.finditer(r"\\w+\\[([\\d,]+)\\]", text)
            if int(m.group(1).split(",")[-1]) in wide]
    assert not rows, rows[:5]

    f, m = step(flat, batch, key)
    spec0 = X.make_flat_spec(wp)
    f1, m1 = jax.jit(P.make_flat_train_step(cfg, proto, spec0.unravel_row))(
        spec0.flatten(wp), batch, key)
    # rounding of the clip norm, carried through the mix: entries that
    # the mix cancels to near zero keep an absolute error of the large
    # ones' size (measured: 3.0e-7, a third of an ULP of the largest
    # entry), so the floor is 4 ULP of the largest entry
    f1 = np.asarray(f1)
    np.testing.assert_allclose(
        np.asarray(spec.unpad(f)), f1, rtol=1e-6,
        atol=4 * float(np.spacing(np.abs(f1).max())))
    for k in m1:
        np.testing.assert_allclose(np.asarray(m[k]), np.asarray(m1[k]),
                                   rtol=1e-6)
    if cap is not None:
        # every budget realizes the unbounded mesh round, bitwise
        spec_u, step_u, flat_u = mesh_round(None)
        fu, mu = step_u(flat_u, batch, key)
        assert np.array_equal(np.asarray(f), np.asarray(fu))
        for k in mu:
            assert np.array_equal(np.asarray(m[k]), np.asarray(mu[k])), k
    print("LEAF_PASS_OK")
""")


@pytest.mark.parametrize("cap", [None, 64, 257])
def test_mesh_round_holds_no_worker_row_subprocess(cap):
    """The static mesh round on a model=4 host-device mesh, one worker a
    device: its compiled program holds no array with the buffer's width
    (d or padded) on its last axis — the worker's [Wb, d] row is never
    built — it agrees with the single-device round to float32 rounding,
    and every chunk budget gives the unbounded mesh round bitwise."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _LEAF_SCRIPT,
                          "none" if cap is None else str(cap)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "LEAF_PASS_OK" in res.stdout


def _leaf_case(clip, poison=False):
    """(leaf pass, flat pass) results of three MLP workers: the flat
    pass's per-worker value_and_grad and clip on a list of leaves, and on
    the raveled row."""
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp
    W = 3
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=clip, p_dbm=60.0, sigma=0.7)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.stack([a * (1.0 + w) for w in range(W)]),
        mlp.init(jax.random.PRNGKey(0), cfg, input_dim=12))
    spec = X.make_flat_spec(wp)
    flat = spec.flatten(wp)
    if poison:
        flat = flat.at[1, 3].set(jnp.nan)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(W, 4, 12))
                              .astype(np.float32)),
             "y": jnp.asarray(rng.integers(0, 10, (W, 4))
                              .astype(np.int32))}
    offs = spec.leaf_offsets()
    leaves = [flat[:, o:o + n].reshape((W,) + tuple(sh))
              for o, n, sh in zip(offs, spec.leaf_sizes(),
                                  spec.leaf_shapes())]
    by_leaf = jax.jit(P._make_flat_local_pass(cfg, proto,
                                              spec.unravel_leaves))
    by_row = jax.jit(P._make_flat_local_pass(cfg, proto, spec.unravel_row))
    l_a, g_a, n_a = by_leaf(leaves, batch)
    g_a = jnp.concatenate([g.reshape(W, -1) for g in g_a], axis=1)
    return (l_a, g_a, n_a), by_row(flat, batch)


@pytest.mark.parametrize("clip", [1e6, 0.5])
def test_leaf_pass_clips_as_the_row_pass(clip):
    """The mesh round's leaf pass clips as privacy.clip_gradient_tree does
    on the row: the same losses; the gradient equal where every norm is
    under g_max (scale 1), within rounding where the clip scales it; the
    pre-clip norms within rounding (one sum per leaf, then their sum)."""
    (l_a, g_a, n_a), (l_b, g_b, n_b) = _leaf_case(clip)
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_b))
    np.testing.assert_allclose(np.asarray(n_a), np.asarray(n_b), rtol=1e-6)
    if clip > float(np.max(n_b)):
        np.testing.assert_array_equal(np.asarray(g_a), np.asarray(g_b))
    else:
        assert float(np.min(n_b)) > clip    # every worker scaled
        np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_b),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(g_a), axis=1), clip, rtol=1e-6)


def test_leaf_pass_zeroes_a_non_finite_worker():
    """A non-finite gradient zeroes every leaf of that worker's gradient
    and reports its norm as 0, as the row pass does; the other workers
    are untouched."""
    (_, g_a, n_a), (_, g_b, n_b) = _leaf_case(0.5, poison=True)
    g_a, n_a = np.asarray(g_a), np.asarray(n_a)
    assert not np.isfinite(
        np.asarray(_leaf_case(1e30, poison=True)[1][0])[1])
    assert n_a[1] == 0.0 and not np.any(g_a[1])
    np.testing.assert_array_equal(g_a[1], np.asarray(g_b)[1])
    assert np.all(n_a[[0, 2]] > 0)
    np.testing.assert_allclose(g_a[[0, 2]], np.asarray(g_b)[[0, 2]],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# chunk plan: seeded property sweeps over pytrees x layouts x budgets
# (plain loops — the offline CI image has no hypothesis package)
# ---------------------------------------------------------------------------


def test_chunk_plan_property_sweep():
    """The ChunkPlan contract (repro.shard.layout): chunks tile [0, d)
    exactly once in order; every chunk lies within ONE leaf and ONE shard
    window; no chunk exceeds the budget; exec_segments() partitions
    [0, shard_width) into budget-bounded spans."""
    from repro.shard import plan_chunks
    rng = np.random.default_rng(20260809)
    for _ in range(40):
        sizes = [int(rng.integers(1, 300))
                 for _ in range(int(rng.integers(1, 8)))]
        d = sum(sizes)
        S = int(rng.choice([1, 2, 3, 4, 8]))
        layout = ShardLayout(d, S)
        budget = rng.choice([0, 1, 7, 64, 500])
        budget = None if budget == 0 else int(budget)
        plan = plan_chunks(layout, sizes, budget)
        label = f"sizes={sizes} S={S} budget={budget}"

        assert plan.chunks[0].start == 0, label
        assert plan.chunks[-1].stop == d, label
        for a, b in zip(plan.chunks[:-1], plan.chunks[1:]):
            assert a.stop == b.start, label
        offs = np.cumsum([0] + sizes)
        sw = layout.shard_width
        for c in plan.chunks:
            assert c.cols > 0, label
            if budget is not None:
                assert c.cols <= budget, label
            assert offs[c.leaf] <= c.start < c.stop <= offs[c.leaf + 1], \
                label
            assert c.shard == c.start // sw, label
            assert c.shard * sw <= c.start and \
                c.stop <= (c.shard + 1) * sw, label
            assert c.local_start == c.start - c.shard * sw, label
            assert c.local_stop == c.stop - c.shard * sw, label

        segs = plan.exec_segments()
        assert segs[0][0] == 0 and segs[-1][1] == sw, label
        for (a0, b0), (a1, b1) in zip(segs[:-1], segs[1:]):
            assert b0 == a1, label
        for a, b in segs:
            assert b > a, label
            if budget is not None:
                assert b - a <= budget, label

        meta = plan.to_meta()
        assert meta["n_chunks"] == len(plan.chunks)
        assert meta["max_chunk_cols"] == budget


def test_flat_spec_chunk_plan_property_sweep():
    """FlatSpec surface of the plan: leaf boundaries come from the spec's
    ravel order, the plan is lazily cached, layout_meta round-trips it,
    and the unsharded spec has no plan."""
    rng = np.random.default_rng(7)
    for _ in range(10):
        tree = {f"l{i}": jnp.zeros(
                    (3, int(rng.integers(1, 9)), int(rng.integers(1, 9))),
                    jnp.float32)
                for i in range(int(rng.integers(1, 5)))}
        S = int(rng.choice([2, 3, 4]))
        cap = int(rng.choice([1, 13, 200]))
        spec = X.make_flat_spec(tree, n_shards=S, max_chunk_cols=cap)
        plan = spec.chunk_plan
        assert plan is spec.chunk_plan          # cached
        assert plan.max_chunk_cols == cap
        leaf_offs = spec.leaf_offsets()
        assert sum(spec.leaf_sizes()) == spec.d
        for c in plan.chunks:
            off = leaf_offs[c.leaf]
            end = off + spec.leaf_sizes()[c.leaf]
            assert off <= c.start < c.stop <= end
        meta = spec.layout_meta()
        assert meta["chunk_plan"] == {"max_chunk_cols": cap,
                                      "n_chunks": len(plan.chunks)}
    spec0 = X.make_flat_spec({"a": jnp.zeros((3, 4), jnp.float32)})
    assert spec0.chunk_plan is None
    assert "chunk_plan" not in spec0.layout_meta()


def test_chunk_plan_validation_errors():
    from repro.shard import plan_chunks
    layout = ShardLayout(100, 2)
    with pytest.raises(ValueError, match="leaf sizes"):
        plan_chunks(layout, [60, 60])
    with pytest.raises(ValueError, match="max_chunk_cols"):
        plan_chunks(layout, [100], max_chunk_cols=0)
    with pytest.raises(ValueError, match="requires a ShardLayout"):
        X.FlatSpec({"a": jnp.zeros((3, 4), jnp.float32)},
                   max_chunk_cols=16)
    with pytest.raises(ValueError, match="max_chunk_cols"):
        X.make_flat_spec({"a": jnp.zeros((3, 4), jnp.float32)},
                         n_shards=2, max_chunk_cols=-3).chunk_plan
