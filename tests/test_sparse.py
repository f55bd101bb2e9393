"""repro.net.sparse + the O(N·k) mixing engine (ISSUE 9 tentpole).

The load-bearing guarantees, asserted over seeded unit-disk draws and
degree caps at N ∈ {8, 32, 128}:

* GRAPH — ``geometry.sparse_metropolis`` emits a padded neighbor list
  (idx self-pointing / w exactly 0 in padded slots) whose densification
  is symmetric, doubly stochastic, degree-capped at k, a subgraph of the
  unit-disk graph, churn-mask aware, and independent of the ``block``
  build transient (bitwise). With k ≥ the max realized disk degree the
  capped graph IS the disk graph.
* KERNEL — the sparse fused round draws the BITWISE-identical noise
  stream as the dense kernel (identity graph ⇒ bitwise-equal rounds) and
  reproduces the dense reference within slot-order summation ULPs on any
  graph (DESIGN.md §15: the dense path stays the small-N reference).
* ε — the graph-aware accountant consumes the SparseW directly: per-
  receiver budgets and σ calibration match the dense-W formula to float32
  summation ULPs, listening masks exactly.
* CHECKPOINT — the padded-neighbor layout descriptor round-trips through
  save_flat/restore_flat metadata, buffer bitwise.
* SHARDING — the worker-axis shard_map step (repro.shard.worker) matches
  the unsharded sparse step with bitwise per-row loss/grad metrics and a
  ULP-close buffer (the mix chain FMA-fuses differently around the
  all_gather — the association caveat its docstring documents).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange as X
from repro.core import privacy
from repro.core import protocol as P
from repro.kernels.dp_mix import ops as mix_ops
from repro.net import geometry as G
from repro.net.sparse import SparseW, isolated_count, sparsify_dense

SWEEP = [(8, 2), (8, 4), (32, 3), (32, 6), (128, 4), (128, 12)]


def _geo(radius, area=100.0):
    return G.GeometryConfig(area=area, comm_radius=radius)


def _pos(key, n, area=100.0):
    return jax.random.uniform(key, (n, 2), jnp.float32) * area


def _radius(n, area=100.0):
    # ~8 expected in-disk neighbors regardless of N: keeps every sweep
    # point in the genuinely-sparse regime without disconnecting N=8
    return float(area * np.sqrt(8.0 / (np.pi * n)))


# ---------------------------------------------------------------------------
# graph builder: seeded property sweep over draws, caps, masks, block sizes
# ---------------------------------------------------------------------------


def test_sparse_metropolis_property_sweep():
    for trial, (n, k) in enumerate(SWEEP):
        key = jax.random.PRNGKey(100 + trial)
        kp, km = jax.random.split(key)
        pos = _pos(kp, n)
        r = _radius(n)
        mask = None
        if trial % 2:   # alternate draws exercise the churn mask
            mask = jax.random.bernoulli(km, 0.8, (n,))
        sw = G.sparse_metropolis(_geo(r), pos, k, mask=mask)
        assert isinstance(sw, SparseW)
        assert sw.idx.shape == (n, k) and sw.w.shape == (n, k)
        idx = np.asarray(sw.idx)
        w = np.asarray(sw.w)
        rows = np.arange(n)[:, None]
        # padded slots: self-pointing, exactly zero weight
        assert np.all(idx[w == 0] == np.broadcast_to(rows, (n, k))[w == 0])
        assert np.all(w >= 0)
        # realized edges respect the disk, the mask, and the cap
        d2 = np.sum((np.asarray(pos)[:, None] - np.asarray(pos)[None]) ** 2,
                    axis=-1)
        real = w > 0
        assert np.all(d2[rows.repeat(k, 1)[real], idx[real]] <= r * r + 1e-4)
        assert np.all(np.sum(real, axis=1) <= k)
        if mask is not None:
            act = np.asarray(mask) > 0
            assert not np.any(real[~act])          # inactive rows: empty
            assert np.all(act[idx[real]])          # no edge INTO inactive
        # densification: symmetric, doubly stochastic, zero-padded clean
        Wd = np.asarray(sw.dense())
        np.testing.assert_allclose(Wd, Wd.T, atol=1e-6)
        np.testing.assert_allclose(Wd.sum(axis=1), 1.0, atol=1e-5)
        # block-built graph is BITWISE the unblocked one (pure data
        # movement; the [block, N] transient is the whole point)
        for block in (5, 16):
            sb = G.sparse_metropolis(_geo(r), pos, k, mask=mask, block=block)
            assert np.array_equal(np.asarray(sb.idx), idx)
            assert np.array_equal(np.asarray(sb.w), w)
        # off_degree matches the dense derivation
        np.testing.assert_array_equal(
            np.asarray(sw.off_degree()), np.sum(real, axis=1))


def test_capped_graph_is_disk_graph_when_k_large():
    """k ≥ max disk degree ⇒ mutual-kNN ∩ disk == disk, and the sparse
    Metropolis weights reproduce the dense metropolis_weights path."""
    for n in (8, 32):
        pos = _pos(jax.random.PRNGKey(7 + n), n)
        r = _radius(n) * 1.5
        adj = G.adjacency(_geo(r), pos)
        sw = G.sparse_metropolis(_geo(r), pos, k=n - 1)
        Wd = np.asarray(G.metropolis_weights(adj))
        Ws = np.asarray(sw.dense())
        assert np.array_equal(Ws > 0, Wd > 0)
        np.testing.assert_allclose(Ws, Wd, atol=2e-6)


def test_fallback_bridges_isolated_workers():
    """An out-of-radius worker is isolated without the fallback and gets
    exactly one nearest-neighbor listen edge with it (satellite 1)."""
    n = 12
    pos = _pos(jax.random.PRNGKey(3), n, area=50.0)
    pos = pos.at[0].set(jnp.array([5000.0, 5000.0]))   # far off-grid
    r = 40.0
    sw = G.sparse_metropolis(_geo(r), pos, k=4)
    assert int(isolated_count(sw)) >= 1
    assert float(sw.off_degree()[0]) == 0.0
    swf = G.sparse_metropolis(_geo(r), pos, k=4, fallback=True)
    assert int(isolated_count(swf)) == 0
    assert float(swf.off_degree()[0]) == 1.0
    # churned-out workers are not "isolated" — the mask drops exactly
    # the inactive zero-degree worker from the count
    mask = jnp.ones((n,)).at[0].set(0.0)
    swm = G.sparse_metropolis(_geo(r), pos, k=4, mask=mask)
    assert float(swm.off_degree()[0]) == 0.0
    assert (int(isolated_count(swm, mask=mask))
            == int(isolated_count(swm)) - 1)
    # dense adjacency fallback bridges the same worker
    adjf = G.adjacency(_geo(r), pos, fallback=True)
    assert float(jnp.sum(adjf[0])) > 0.0


def test_sparsify_dense_roundtrip():
    """k ≥ realized degree ⇒ sparsify_dense is lossless: densifying the
    compressed form reproduces the matrix bitwise (top_k keeps exact
    values; the diagonal is copied, not recomputed)."""
    pos = _pos(jax.random.PRNGKey(11), 16)
    W = G.metropolis_weights(G.adjacency(_geo(_radius(16)), pos))
    offd = (np.asarray(W) > 0) & ~np.eye(16, dtype=bool)
    k = int(offd.sum(axis=1).max())
    sw = sparsify_dense(W, max(k, 1))
    assert np.array_equal(np.asarray(sw.dense()), np.asarray(W))


# ---------------------------------------------------------------------------
# kernel: noise-stream invariance (bitwise) + dense reference (ULP) sweep
# ---------------------------------------------------------------------------


def _round_args(key, n, d):
    ks = jax.random.split(key, 4)
    p = jax.random.normal(ks[0], (n, d), jnp.float32)
    g = jax.random.normal(ks[1], (n, d), jnp.float32) * 0.1
    amp = jax.random.uniform(ks[2], (n,)) + 0.5
    mscale = jax.random.uniform(ks[3], (n,)) * 0.3
    return p, g, amp, mscale


def test_sparse_round_identity_graph_ulp():
    """Empty neighbor lists (self_w = 1) remove the slot-order summation
    freedom entirely, so identity-graph disagreement with the dense W = I
    round bounds the FUSION noise floor: the two programs draw the
    bitwise-identical counter-addressed noise and differ only in how XLA
    FMA-contracts the elementwise chain — a handful of final-place ULPs,
    an order tighter than the graph-sweep tolerance."""
    n, d = 16, 40
    p, g, amp, mscale = _round_args(jax.random.PRNGKey(0), n, d)
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, 2))
    sw = SparseW(idx=rows, w=jnp.zeros((n, 2), jnp.float32),
                 self_w=jnp.ones((n,), jnp.float32))
    for noisy in (True, False):
        ref = mix_ops.dp_mix_round(
            p, g, jnp.int32(77), jnp.eye(n), amp, 2.0, 0.3, gamma=0.05,
            eta=0.4, m_scale=mscale, noisy=noisy, impl="jnp")
        out = mix_ops.dp_mix_round_sparse(
            p, g, jnp.int32(77), sw, amp, 2.0, 0.3, gamma=0.05,
            eta=0.4, m_scale=mscale, noisy=noisy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"noisy={noisy}")


def test_sparse_round_column_window_tiling_bitwise():
    """The repro.shard column-window hooks on the SPARSE kernel: two
    half-width windows called with their global col0 and the canonical
    counter_width reassemble the whole-buffer round BITWISE — the noise
    counters (row·counter_width + col0 + col) tile the exact unsharded
    stream, the same contract the dense kernel ships for model sharding."""
    n, d = 16, 256
    sw = G.sparse_metropolis(_geo(_radius(n)), _pos(jax.random.PRNGKey(2), n),
                             4)
    p, g, amp, mscale = _round_args(jax.random.PRNGKey(3), n, d)
    full = mix_ops.dp_mix_round_sparse(
        p, g, jnp.int32(21), sw, amp, 2.0, 0.3, gamma=0.05, eta=0.4,
        m_scale=mscale)
    halves = [mix_ops.dp_mix_round_sparse(
        p[:, c0:c0 + 128], g[:, c0:c0 + 128], jnp.int32(21), sw, amp,
        2.0, 0.3, gamma=0.05, eta=0.4, m_scale=mscale, col0=c0,
        counter_width=d) for c0 in (0, 128)]
    assert np.array_equal(np.asarray(full),
                          np.concatenate([np.asarray(h) for h in halves],
                                         axis=1))


def test_sparse_round_matches_dense_reference_sweep():
    """The tentpole equivalence: over seeded unit-disk draws and degree
    caps, mixing through the neighbor list reproduces the dense-W fused
    round within slot-order summation ULPs — noise stream included."""
    for trial, (n, k) in enumerate(SWEEP):
        key = jax.random.PRNGKey(200 + trial)
        kp, kr = jax.random.split(key)
        sw = G.sparse_metropolis(_geo(_radius(n)), _pos(kp, n), k)
        p, g, amp, mscale = _round_args(kr, n, 40)
        for noisy in (True, False):
            ref = mix_ops.dp_mix_round(
                p, g, jnp.int32(5 + trial), sw.dense(), amp, 2.0, 0.3,
                gamma=0.05, eta=0.4, m_scale=mscale, noisy=noisy,
                impl="jnp")
            out = mix_ops.dp_mix_round_sparse(
                p, g, jnp.int32(5 + trial), sw, amp, 2.0, 0.3,
                gamma=0.05, eta=0.4, m_scale=mscale, noisy=noisy)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
                err_msg=f"N={n} k={k} noisy={noisy}")


def _proto(**kw):
    base = dict(scheme="dwfl", n_workers=8, gamma=0.05, eta=0.4, clip=1.0,
                p_dbm=60.0, sigma=0.7, sigma_m=0.5, channel_model="dynamic",
                scenario="iot_dense")
    base.update(kw)
    return P.ProtocolConfig(**base)


def test_exchange_sparse_plan_matches_dense():
    """The simulator emits a SparseW under sparse_neighbors>0, and the
    planned round through it matches the dense plan built from the SAME
    graph (W.dense()) to summation ULPs — the ExchangeSpec dispatch layer
    preserves the kernel equivalence."""
    proto = _proto(sparse_neighbors=3)
    sim = proto.simulator()
    net = sim.init(jax.random.PRNGKey(1))
    _, chan, _, Ws = jax.jit(sim.round)(jax.random.PRNGKey(2), net)
    assert isinstance(Ws, SparseW)
    assert (Ws.n_workers, Ws.k) == (8, 3)
    k_x = jax.random.PRNGKey(3)
    plan_s = X.plan_dynamic_sparse(proto, chan, k_x, W_arg=Ws)
    plan_d = X.plan_dynamic(proto, chan, k_x, W_arg=Ws.dense())
    p, g, _, _ = _round_args(jax.random.PRNGKey(4), 8, 24)
    out_s = mix_ops.dp_mix_round_plan(p, g, jnp.int32(9), plan_s,
                                      gamma=0.05, eta=0.4)
    out_d = mix_ops.dp_mix_round_plan(p, g, jnp.int32(9), plan_d,
                                      gamma=0.05, eta=0.4)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ε accounting: the graph-aware budgets consume the SparseW directly
# ---------------------------------------------------------------------------


def test_epsilon_sparse_matches_dense_formula():
    proto = _proto(sparse_neighbors=3, n_workers=32,
                   scenario="mesh_sparse")
    sim = proto.simulator()
    net = sim.init(jax.random.PRNGKey(5))
    key = jax.random.PRNGKey(6)
    for r in range(3):
        net, chan, _, Ws = jax.jit(sim.round)(jax.random.fold_in(key, r),
                                              net)
        eps_s = privacy.epsilon_dwfl_traced(0.05, 1.0, chan, 1e-5, W=Ws)
        eps_d = privacy.epsilon_dwfl_traced(0.05, 1.0, chan, 1e-5,
                                            W=Ws.dense())
        # same formula, gather-sum vs dense-contraction order: ULP-level
        np.testing.assert_allclose(np.asarray(eps_s), np.asarray(eps_d),
                                   rtol=1e-5, atol=1e-7)
        # listening masks (which receivers hold ANY budget) agree exactly
        assert np.array_equal(np.asarray(eps_s) > 0, np.asarray(eps_d) > 0)
        sig_s = privacy.sigma_for_epsilon_traced(1.0, 0.05, 1.0, chan,
                                                 1e-5, W=Ws)
        sig_d = privacy.sigma_for_epsilon_traced(1.0, 0.05, 1.0, chan,
                                                 1e-5, W=Ws.dense())
        np.testing.assert_allclose(np.asarray(sig_s), np.asarray(sig_d),
                                   rtol=1e-5)


def test_epsilon_trajectory_sparse_deterministic():
    """The per-round ε computed from a stacked SparseW trajectory (the
    scan telemetry path) is bitwise the round-at-a-time accounting —
    SparseW stacks along scan outputs like any dense leaf."""
    proto = _proto(sparse_neighbors=3)
    sim = proto.simulator()
    net = sim.init(jax.random.PRNGKey(8))
    chans, _, Ws = sim.trajectory(jax.random.PRNGKey(9), 4, net)
    assert isinstance(Ws, SparseW) and Ws.idx.shape == (4, 8, 3)
    per_round = jax.vmap(
        lambda ch, sw: privacy.epsilon_dwfl_traced(0.05, 1.0, ch, 1e-5,
                                                   W=sw))(chans, Ws)
    for r in range(4):
        ch_r = jax.tree_util.tree_map(lambda a: a[r], chans)
        sw_r = jax.tree_util.tree_map(lambda a: a[r], Ws)
        one = privacy.epsilon_dwfl_traced(0.05, 1.0, ch_r, 1e-5, W=sw_r)
        assert np.array_equal(np.asarray(per_round[r]), np.asarray(one))


# ---------------------------------------------------------------------------
# checkpoint: the padded-neighbor layout descriptor round-trips
# ---------------------------------------------------------------------------


def test_checkpoint_sparse_layout_meta_roundtrip(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=12)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (8,) + a.shape), params)
    spec = X.make_flat_spec(wp)
    flat = spec.flatten(wp)
    sw = G.sparse_metropolis(_geo(_radius(8)), _pos(jax.random.PRNGKey(1), 8),
                             3)
    path = str(tmp_path / "ck")
    ckpt.save_flat(path, flat, spec, step=7,
                   metadata={"sparse_neighbors": 3,
                             "sparse_w": sw.layout_meta()})
    flat2, _, manifest = ckpt.restore_flat(path, spec)
    assert np.array_equal(np.asarray(flat2), np.asarray(flat))
    meta = manifest["metadata"]
    assert meta["sparse_neighbors"] == 3
    assert meta["sparse_w"] == {"format": "padded-neighbor-v1",
                                "n_workers": 8, "k": 3,
                                "pad": "self-index-zero-weight"}


# ---------------------------------------------------------------------------
# the dense-mixing static checker (satellite 2): unit-level
# ---------------------------------------------------------------------------


def test_dense_mixing_checker():
    from repro.analysis import Severity, check_dense_mixing

    def dense_mix(W, z):
        return W @ z

    def sparse_mix(sw, z):
        acc = sw.self_w[:, None] * z
        for s in range(sw.k):
            acc = acc + sw.w[:, s:s + 1] * z[sw.idx[:, s]]
        return acc

    n = 8
    W = jnp.eye(n) * 0.5
    z = jnp.ones((n, 16), jnp.float32)
    sw = sparsify_dense(jnp.ones((n, n)) / n, 3)
    bad = jax.make_jaxpr(dense_mix)(W, z)
    good = jax.make_jaxpr(sparse_mix)(sw, z)
    errs = [f for f in check_dense_mixing(bad, "t", sparse=True, n_workers=n)
            if f.severity == Severity.ERROR]
    assert len(errs) == 1 and "[N, N]-shaped contraction" in errs[0].message
    clean = check_dense_mixing(good, "t", sparse=True, n_workers=n)
    assert all(f.severity == Severity.INFO for f in clean)
    # dense-mode programs have no contract: not-applicable INFO only
    na = check_dense_mixing(bad, "t", sparse=False, n_workers=n)
    assert [f.severity for f in na] == [Severity.INFO]
    # a model matmul whose inner dim merely EQUALS N is not flagged
    ok = jax.make_jaxpr(dense_mix)(jnp.ones((3, n), jnp.float32),
                                   jnp.ones((n, 16), jnp.float32))
    assert all(f.severity == Severity.INFO
               for f in check_dense_mixing(ok, "t", sparse=True,
                                           n_workers=n))


# ---------------------------------------------------------------------------
# worker-axis sharding: 2-device subprocess parity (tests run 1-device)
# ---------------------------------------------------------------------------


_WORKER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.launch import mesh as mesh_lib
    from repro.net.sparse import SparseW
    from repro.shard import (make_worker_sharded_dynamic_flat_train_step,
                             worker_partition_spec)
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp

    W, DIM, BATCH = 8, 12, 4
    cfg = get_arch("dwfl-paper").replace(d_model=8)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                             sigma_m=0.5, channel_model="dynamic",
                             scenario="iot_dense", sparse_neighbors=3)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=DIM)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(W, BATCH, DIM))
                              .astype(np.float32)),
             "y": jnp.asarray(rng.integers(0, 10, (W, BATCH))
                              .astype(np.int32))}
    spec = X.make_flat_spec(wp)
    flat0 = spec.flatten(wp)
    sim = proto.simulator()
    net0 = sim.init(jax.random.PRNGKey(1))
    _, chan, _, Ws = jax.jit(sim.round)(jax.random.PRNGKey(2), net0)
    assert isinstance(Ws, SparseW)

    base = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                  spec.unravel_row))
    f1, m1 = base(flat0, batch, jax.random.PRNGKey(42), chan, Ws)

    mesh = mesh_lib.make_worker_mesh(2)
    flat = jax.device_put(flat0, NamedSharding(mesh,
                                               worker_partition_spec()))
    step = make_worker_sharded_dynamic_flat_train_step(cfg, proto, spec,
                                                       mesh=mesh)
    f2, m2 = step(flat, batch, jax.random.PRNGKey(42), chan, Ws)
    # buffer: ULP-close (FMA association across the all_gather boundary)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=1e-5, atol=3e-5)
    # per-row losses/grads are computed locally and gathered, but XLA
    # picks each program's reduction strategy for the final mean, and
    # param_norm psums per-shard partials: all three means agree at the
    # mesh-mean ULP tolerance (test_shard's parity script)
    for k in ("loss", "grad_norm", "param_norm"):
        np.testing.assert_allclose(np.asarray(m1[k]), np.asarray(m2[k]),
                                   rtol=1e-6, err_msg=k)
    print("WORKER_SHARD_OK")
""")


@pytest.mark.slow
def test_worker_shard_round_parity_subprocess():
    """Acceptance: on a 2-device ``workers`` mesh the row-sharded sparse
    round matches the unsharded dynamic flat step — metric means and
    buffer ULP-close (repro.shard.worker
    docstring documents why the buffer is not bitwise)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _WORKER_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "WORKER_SHARD_OK" in res.stdout
