"""Scan-fused trajectory engine (ISSUE 4 tentpole).

The load-bearing guarantee: chunking is INVISIBLE to the computation.
For every driver path (static, dynamic, fleet, and the baselines' planless
round), running T rounds on the flat [W, d] buffer as K-chunked
``lax.scan`` programs produces BITWISE-identical final params, channel
trajectories, mixing-matrix logs and metrics to the per-round
one-dispatch-per-round loop over the same body — and the realized PRNG
stream depends only on the initial key and the round index, never on
where the chunk boundaries fall (K ∤ T included).
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: deterministic fallback shim
    from _hypo_fallback import given, settings, st

from repro.core import exchange as X
from repro.core import protocol as P
from repro.core import trajectory as TJ
from repro.data.device import (ClassificationStore, LMStore,
                               store_from_batcher)
from repro.data.pipeline import FederatedBatcher, LMBatcher

W, R = 5, 2
DIM, BATCH, NDATA = 12, 4, 160


def _cfg():
    from repro.configs.registry import get_arch
    return get_arch("dwfl-paper").replace(d_model=8)


def _data(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(NDATA, DIM)).astype(np.float32)
    y = rng.integers(0, 10, NDATA).astype(np.int32)
    parts = [np.arange(w, NDATA, W) for w in range(W)]
    return x, y, parts


def _store(seed=0):
    x, y, parts = _data(seed)
    return ClassificationStore.build(x, y, parts, BATCH)


def _wp(cfg, key=None):
    import repro.models.mlp as mlp
    params = mlp.init(key if key is not None else jax.random.PRNGKey(0),
                      cfg, input_dim=DIM)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)


def _flat(wp, lead_axes=1):
    """(flat buffer, FlatSpec) of a worker-stacked tree."""
    spec = X.make_flat_spec(wp, lead_axes=lead_axes)
    return spec.flatten(wp), spec


def _proto(**kw):
    base = dict(scheme="dwfl", n_workers=W, gamma=0.05, eta=0.4, clip=1.0,
                p_dbm=60.0, sigma=0.7, sigma_m=0.5)
    base.update(kw)
    return P.ProtocolConfig(**base)


def _assert_tree_equal(a, b, what=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x1, x2 in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2),
                                      err_msg=what)


def _assert_tree_ulp_close(a, b, what=""):
    """Float-identical up to XLA's per-program FMA contraction (~2 ULP).

    Used ONLY for the fleet-flat configuration: the R-vmapped dp_mix
    matmul lands in different fusion clusters for different compiled
    programs (scan lengths), and XLA CPU contracts a*b+c into fma in some
    of them — a 1-2 ULP rounding difference with identical PRNG draws.
    Every other configuration is asserted BITWISE (DESIGN.md §10)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x1, x2 in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=5e-6, atol=5e-7, err_msg=what)


def _run_chunked(body, carry, partition):
    runner = TJ.ChunkRunner(body, donate=False)
    outs = []
    for k in partition:
        carry, out = runner.run(carry, k)
        outs.append(out)
    return carry, TJ.concat_chunks(outs)


# ---------------------------------------------------------------------------
# scan-vs-loop bitwise equivalence, all three paths
# ---------------------------------------------------------------------------


def test_static_scan_equals_loop():
    cfg = _cfg()
    wp, spec = _flat(_wp(cfg))
    body = TJ.make_round_body(cfg, _proto(), _store(), spec=spec)
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(3), wp)
    c_loop, out_loop = TJ.run_per_round(body, carry0, 7)
    c_scan, out_scan = _run_chunked(body, carry0, (3, 3, 1))
    _assert_tree_equal(c_loop.key, c_scan.key, "carry key")
    _assert_tree_equal(c_loop.params, c_scan.params, "final params")
    _assert_tree_equal(out_loop["metrics"], out_scan["metrics"], "metrics")


@pytest.mark.parametrize("scheme", ["orthogonal", "centralized"])
def test_baseline_scan_equals_loop(scheme):
    """The baselines' planless round (local step, then their exchange on
    the [W, d] buffer as one leaf) keeps the chunk-invariance contract."""
    cfg = _cfg()
    wp, spec = _flat(_wp(cfg))
    body = TJ.make_round_body(cfg, _proto(scheme=scheme), _store(),
                              spec=spec)
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(3), wp)
    c_loop, out_loop = TJ.run_per_round(body, carry0, 7)
    c_scan, out_scan = _run_chunked(body, carry0, (3, 3, 1))
    _assert_tree_equal(c_loop.key, c_scan.key, "carry key")
    _assert_tree_equal(c_loop.params, c_scan.params, "final params")
    _assert_tree_equal(out_loop["metrics"], out_scan["metrics"], "metrics")


def test_dynamic_scan_equals_loop():
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense")
    sim = proto.simulator()
    wp, spec = _flat(_wp(cfg))
    body = TJ.make_round_body(cfg, proto, _store(), sim=sim, spec=spec)
    net0 = sim.init(jax.random.PRNGKey(4))
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(5), wp, net0)
    c_loop, out_loop = TJ.run_per_round(body, carry0, 6)
    c_scan, out_scan = _run_chunked(body, carry0, (4, 2))
    _assert_tree_equal(c_loop.params, c_scan.params, "final params")
    _assert_tree_equal(c_loop.net, c_scan.net, "net state")
    _assert_tree_equal(out_loop["chan"], out_scan["chan"], "chan trajectory")
    _assert_tree_equal(out_loop["W"], out_scan["W"], "W log")
    assert out_scan["chan"].h.shape == (6, W)
    assert out_scan["W"].shape == (6, W, W)


def test_fleet_scan_equals_loop():
    from repro.fleet import FleetEngine
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense",
                   replicates=R)
    fleet = FleetEngine(proto)
    wp1 = _wp(cfg)
    wp, spec = _flat(jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), wp1),
        lead_axes=2)
    body = TJ.make_round_body(cfg, proto, _store(), fleet=fleet, spec=spec)
    net0 = fleet.init(jax.random.PRNGKey(6))
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(7), wp, net0)
    c_loop, out_loop = TJ.run_per_round(body, carry0, 5)
    c_scan, out_scan = _run_chunked(body, carry0, (2, 2, 1))
    # channel/W streams are pure PRNG functions — bitwise; params are
    # ULP-close (per-program FMA contraction of the vmapped dp_mix matmul)
    _assert_tree_ulp_close(c_loop.params, c_scan.params, "final params")
    _assert_tree_equal(out_loop["chan"], out_scan["chan"], "chan trajectory")
    _assert_tree_equal(out_loop["W"], out_scan["W"], "W log")
    assert out_scan["chan"].h.shape == (5, R, W)
    assert out_scan["metrics"]["loss"].shape == (5, R)
    # report layout: replicate-major [R, T, ...] for the batched accounting
    rm = TJ.replicate_major(out_scan["chan"])
    assert rm.h.shape == (R, 5, W)
    np.testing.assert_array_equal(np.asarray(rm.h[:, 2]),
                                  np.asarray(out_scan["chan"].h[2]))


# ---------------------------------------------------------------------------
# chunk boundaries cannot change the realized PRNG stream (K ∤ T)
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(k=st.integers(min_value=1, max_value=9))
def test_chunk_partition_preserves_prng_stream(k):
    """Any chunk length K (divisor of T or not) realizes the SAME stream:
    identical channel draws, params and metrics as the K=T single chunk."""
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense")
    sim = proto.simulator()
    wp, spec = _flat(_wp(cfg))
    body = TJ.make_round_body(cfg, proto, _store(), sim=sim, spec=spec)
    net0 = sim.init(jax.random.PRNGKey(8))
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(9), wp, net0)
    T = 8
    ref_carry, ref_out = _run_chunked(body, carry0, (T,))
    partition = [k] * (T // k) + ([T % k] if T % k else [])
    got_carry, got_out = _run_chunked(body, carry0, partition)
    _assert_tree_equal(ref_out["chan"], got_out["chan"],
                       f"chan stream, partition={partition}")
    _assert_tree_equal(ref_carry.params, got_carry.params,
                       f"params, partition={partition}")
    _assert_tree_equal(ref_out["metrics"], got_out["metrics"],
                       f"metrics, partition={partition}")


# ---------------------------------------------------------------------------
# chunk planning / auto sizing
# ---------------------------------------------------------------------------


def test_plan_chunks_covers_and_cuts_at_eval_boundaries():
    plan = TJ.plan_chunks(201, 32, 50)
    assert sum(n for n, _ in plan) == 201
    assert all(1 <= n <= 32 for n, _ in plan)
    # eval flags exactly at rounds t % 50 == 0 (t = cumulative-1)
    done, evals = 0, []
    for n, ev in plan:
        done += n
        if ev:
            evals.append(done - 1)
        else:
            assert (done - 1) % 50 != 0
    assert evals == [0, 50, 100, 150, 200]


def test_plan_chunks_no_eval():
    plan = TJ.plan_chunks(10, 4, 0)
    assert plan == [(4, False), (4, False), (2, False)]


def test_plan_chunks_degenerate():
    assert TJ.plan_chunks(0, 4, 10) == []
    with pytest.raises(ValueError):
        TJ.plan_chunks(5, 0, 10)
    with pytest.raises(ValueError):
        TJ.ChunkRunner(lambda c: (c, {})).run(None, 0)


def test_auto_chunk():
    assert TJ.auto_chunk(50) == 50
    assert TJ.auto_chunk(50, coherence_rounds=20) == 20
    assert TJ.auto_chunk(10, coherence_rounds=20) == 10    # <= eval interval
    assert TJ.auto_chunk(50, coherence_rounds=10**9) == 50  # static preset
    assert TJ.auto_chunk(0, coherence_rounds=None) == 512
    assert TJ.auto_chunk(0, coherence_rounds=64) == 64


# ---------------------------------------------------------------------------
# device-resident data store
# ---------------------------------------------------------------------------


def test_class_store_samples_within_partitions():
    x, y, parts = _data()
    # make features identify their global index so gathers are auditable
    x[:, 0] = np.arange(NDATA)
    store = ClassificationStore.build(x, y, parts, BATCH)
    batch = jax.jit(store.sample)(jax.random.PRNGKey(0))
    assert batch["x"].shape == (W, BATCH, DIM)
    assert batch["y"].shape == (W, BATCH)
    idx = np.asarray(batch["x"][:, :, 0]).astype(np.int64)
    for w in range(W):
        assert set(idx[w].tolist()) <= set(parts[w].tolist())
        np.testing.assert_array_equal(np.asarray(batch["y"][w]),
                                      np.asarray(y[idx[w]]))


def test_class_store_unequal_partitions():
    x, y, _ = _data()
    parts = [np.arange(0, 3), np.arange(3, NDATA)]   # 3 vs 157 samples
    store = ClassificationStore.build(x, y, parts, 8)
    batch = store.sample(jax.random.PRNGKey(1))
    idx0 = set(np.asarray(
        jnp.argmin(jnp.abs(batch["x"][0, :, None, :] - jnp.asarray(x)[None]
                           ).sum(-1), axis=-1)).tolist())
    assert idx0 <= {0, 1, 2}


def test_class_store_fleet_axis_and_key_determinism():
    store = _store()
    k = jax.random.PRNGKey(2)
    br = store.sample_fleet(k, R)
    assert br["x"].shape == (R, W, BATCH, DIM)
    # replicate r IS sample(split(k)[r]) — the fleet/loop anchor
    keys = jax.random.split(k, R)
    for r in range(R):
        one = store.sample(keys[r])
        np.testing.assert_array_equal(np.asarray(br["x"][r]),
                                      np.asarray(one["x"]))
    # same key -> same batch; different key -> different batch
    np.testing.assert_array_equal(np.asarray(store.sample(k)["x"]),
                                  np.asarray(store.sample(k)["x"]))
    assert not np.array_equal(np.asarray(store.sample(k)["x"]),
                              np.asarray(store.sample(
                                  jax.random.PRNGKey(3))["x"]))


def test_lm_store_windows_stay_in_worker_slice():
    n_tok, seq = 4000, 16
    toks = np.arange(n_tok, dtype=np.int32) % 50
    store = LMStore.build(toks, 4, 3, seq)
    batch = store.sample(jax.random.PRNGKey(4))
    assert batch["tokens"].shape == (4, 3, seq)
    per = n_tok // 4
    got = np.asarray(batch["tokens"])
    for w in range(4):
        for b in range(3):
            # windows are contiguous mod-50 runs inside worker w's slice
            seqv = got[w, b].astype(np.int64)
            diffs = np.diff(seqv) % 50
            assert (diffs == 1).all()


def test_store_from_batcher_roundtrip():
    x, y, parts = _data()
    fb = FederatedBatcher(x, y, parts, BATCH, seed=0)
    cs = store_from_batcher(fb)
    assert isinstance(cs, ClassificationStore)
    assert cs.batch == BATCH and cs.n_workers == W
    toks = (np.arange(2000) % 7).astype(np.int32)
    lb = LMBatcher(toks, 4, 2, 8, seed=0)
    ls = store_from_batcher(lb)
    assert isinstance(ls, LMStore)
    assert (ls.batch, ls.seq_len, ls.n_workers) == (2, 8, 4)
    with pytest.raises(TypeError):
        store_from_batcher(object())


# ---------------------------------------------------------------------------
# in-scan telemetry (ISSUE 6): read-only, bitwise-invisible instrumentation
# ---------------------------------------------------------------------------


def _flat_setup(proto_kw, fleet_engine=None):
    """Shared flat-buffer trajectory setup for the telemetry tests."""
    cfg = _cfg()
    proto = _proto(**proto_kw)
    wp = _wp(cfg)
    lead = 1
    if fleet_engine is not None:
        wp = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), wp)
        lead = 2
    _unravel, unravel_row = X.worker_unravelers(wp, lead_axes=lead)
    flat = X.flatten_worker_tree(wp, lead_axes=lead)
    return cfg, proto, flat, unravel_row


def test_static_telemetry_bitwise_invisible_and_consistent():
    """Telemetry ON changes NOTHING about the realized trajectory (params,
    key, metrics bitwise), adds the [K, M] rows, and on the static channel
    the chan-derived columns are the compile-time constants of the
    protocol's channel."""
    from repro import obs
    from repro.obs import telemetry as tl
    cfg, proto, flat, unravel_row = _flat_setup({})
    tele = obs.TelemetrySpec()
    store = _store()
    mk = lambda t: TJ.make_round_body(cfg, proto, store,
                                      unravel_row=unravel_row, telemetry=t)
    key = jax.random.PRNGKey(11)
    T = 6
    c_off, out_off = _run_chunked(mk(None), TJ.TrajCarry(key, flat), (4, 2))
    c_on, out_on = _run_chunked(
        mk(tele), TJ.TrajCarry(key, flat, eps=obs.init_eps_moments()), (4, 2))
    _assert_tree_equal(c_off.params, c_on.params, "params, telemetry on/off")
    _assert_tree_equal(c_off.key, c_on.key, "key, telemetry on/off")
    _assert_tree_equal(out_off["metrics"], out_on["metrics"], "metrics")

    rows = np.asarray(out_on["telemetry"])
    assert rows.shape == (T, tele.n_fields)
    cols = {f: rows[:, i] for i, f in enumerate(tele.fields)}
    np.testing.assert_array_equal(
        cols["loss"], np.asarray(out_on["metrics"]["loss"], np.float32))
    np.testing.assert_array_equal(
        cols["grad_norm"],
        np.asarray(out_on["metrics"]["grad_norm"], np.float32))
    # static channel: the chan-derived columns are round-constant and equal
    # the host-side evaluation on the protocol's channel
    from repro.net.state import TracedChannelState
    chan = TracedChannelState.from_static(proto.channel())
    W_mat = jnp.asarray(proto.mixing_matrix(), jnp.float32)
    ref = {k: float(v) for k, v in chan.telemetry(tele, W_mat).items()}
    ref["epsilon"] = float(tl.epsilon_round(proto, chan, W_mat))
    for name in ("snr_db", "deep_fade", "participation", "epsilon"):
        np.testing.assert_allclose(cols[name], ref[name], rtol=1e-6,
                                   err_msg=name)
    # eps moments: T identical rounds of the constant per-round eps (the
    # widened carry also folds the constant per-round RDP ledger)
    e = ref["epsilon"]
    rdp1 = tl.rdp_round(proto, chan, W_mat)
    np.testing.assert_allclose(
        np.asarray(c_on.eps),
        np.asarray(tl.accumulate_eps(tl.init_eps_moments(),
                                     jnp.float32(e), rdp=rdp1) * T),
        rtol=1e-5)


def test_telemetry_consensus_is_preround_params():
    """Row t of the consensus column is the distance of the params that
    ENTERED round t (row 0 == 0 for a common-start init), as documented in
    trajectory._maybe_instrument."""
    from repro import obs
    from repro.obs import telemetry as tl
    cfg, proto, flat, unravel_row = _flat_setup({})
    tele = obs.TelemetrySpec()
    body = TJ.make_round_body(cfg, proto, _store(),
                              unravel_row=unravel_row, telemetry=tele)
    carry = TJ.TrajCarry(jax.random.PRNGKey(12), flat,
                         eps=obs.init_eps_moments())
    T = 5
    ref = []
    c = carry
    for _ in range(T):
        ref.append(float(tl.consensus_distance(c.params)))
        c, _ = body(c)
    _, out = _run_chunked(body, carry, (T,))
    got = np.asarray(out["telemetry"])[:, tele.fields.index("consensus")]
    assert got[0] < 1e-5                      # broadcast common start
    assert (got[1:] > 1e-3).all()
    np.testing.assert_allclose(got, np.float32(ref), rtol=1e-5, atol=1e-6)


def test_dynamic_telemetry_matches_host_recompute():
    """Dynamic path: telemetry on/off trajectories bitwise identical, and
    every chan-derived column equals the host-side recompute from the
    logged channel states (the epsilon column IS Thm 4.1 per round)."""
    from repro import obs
    from repro.obs import telemetry as tl
    cfg, proto, flat, unravel_row = _flat_setup(
        {"channel_model": "dynamic", "scenario": "iot_dense"})
    sim = proto.simulator()
    tele = obs.TelemetrySpec()
    store = _store()
    mk = lambda t: TJ.make_round_body(cfg, proto, store, sim=sim,
                                      unravel_row=unravel_row, telemetry=t)
    net0 = sim.init(jax.random.PRNGKey(13))
    key = jax.random.PRNGKey(14)
    T = 6
    c_off, out_off = _run_chunked(mk(None),
                                  TJ.TrajCarry(key, flat, net0), (3, 3))
    c_on, out_on = _run_chunked(
        mk(tele),
        TJ.TrajCarry(key, flat, net0, obs.init_eps_moments()), (3, 3))
    _assert_tree_equal(c_off.params, c_on.params, "params, telemetry on/off")
    _assert_tree_equal(out_off["chan"], out_on["chan"], "chan stream")
    _assert_tree_equal(out_off["W"], out_on["W"], "W log")

    rows = np.asarray(out_on["telemetry"])
    cols = {f: rows[:, i] for i, f in enumerate(tele.fields)}
    ref = jax.vmap(lambda ch, w: ch.telemetry(tele, w))(out_on["chan"],
                                                        out_on["W"])
    for name, col in ref.items():
        np.testing.assert_allclose(cols[name], np.asarray(col), rtol=1e-5,
                                   err_msg=name)
    eps_ref = jax.vmap(lambda ch, w: tl.epsilon_round(proto, ch, w))(
        out_on["chan"], out_on["W"])
    np.testing.assert_allclose(cols["epsilon"], np.asarray(eps_ref),
                               rtol=1e-5)
    # carry moments == sum of the per-round moment updates, and their
    # composition agrees with the host-side heterogeneous composition
    from repro.core import accounting, privacy
    rdp_ref = jax.vmap(lambda ch, w: tl.rdp_round(proto, ch, w))(
        out_on["chan"], out_on["W"])
    acc = tl.init_eps_moments()
    for e, r in zip(np.asarray(eps_ref), np.asarray(rdp_ref)):
        acc = tl.accumulate_eps(acc, jnp.float32(e), rdp=jnp.asarray(r))
    np.testing.assert_allclose(np.asarray(c_on.eps), np.asarray(acc),
                               rtol=1e-5)
    e_m, d_m = privacy.compose_from_moments(np.asarray(c_on.eps),
                                            proto.delta)
    e_ref, d_ref = privacy.compose_heterogeneous(
        np.asarray(eps_ref, np.float64), proto.delta)
    np.testing.assert_allclose(e_m, e_ref, rtol=1e-4)
    np.testing.assert_allclose(d_m, d_ref, rtol=1e-6)
    # in-scan RDP ledger == host-side recomputation from the logged
    # channel trajectory, through BOTH the raw per-order sums and the
    # converted budget (ISSUE 10 acceptance: rtol 1e-4)
    np.testing.assert_allclose(
        np.asarray(c_on.eps)[4:], np.asarray(rdp_ref).sum(0), rtol=1e-4)
    e_r, d_r = privacy.compose_from_moments(np.asarray(c_on.eps),
                                            proto.delta, accountant="rdp")
    e_host, _ = accounting.rdp_to_epsilon(
        np.asarray(rdp_ref, np.float64).sum(0), d_r)
    np.testing.assert_allclose(e_r, e_host, rtol=1e-4)
    assert e_r < e_m  # the Rényi ledger is the tighter quote here


def test_fleet_telemetry_shape_and_host_recompute():
    """Fleet path: [K, R, M] rows, per-replicate eps moments, and the
    chan columns match fleet_round_telemetry on the replicate-major log."""
    from repro import obs
    from repro.fleet import FleetEngine, fleet_round_telemetry
    cfg, proto, flat, unravel_row = _flat_setup(
        {"channel_model": "dynamic", "scenario": "iot_dense",
         "replicates": R}, fleet_engine=True)
    fleet = FleetEngine(proto)
    tele = obs.TelemetrySpec()
    mk = lambda t: TJ.make_round_body(cfg, proto, _store(), fleet=fleet,
                                      unravel_row=unravel_row,
                                      telemetry=t)
    net0 = fleet.init(jax.random.PRNGKey(15))
    key = jax.random.PRNGKey(16)
    T = 4
    c_off, out_off = _run_chunked(mk(None),
                                  TJ.TrajCarry(key, flat, net0), (2, 2))
    c_on, out_on = _run_chunked(
        mk(tele), TJ.TrajCarry(key, flat, net0, obs.init_eps_moments(R)),
        (2, 2))
    # channel/W streams bitwise; params ULP-close (fleet-flat FMA
    # contraction across different fusion clusters — see the scan-vs-loop
    # fleet test)
    _assert_tree_equal(out_off["chan"], out_on["chan"], "chan stream")
    _assert_tree_ulp_close(c_off.params, c_on.params, "params on/off")

    rows = np.asarray(out_on["telemetry"])
    assert rows.shape == (T, R, tele.n_fields)
    from repro.core import accounting
    assert np.asarray(c_on.eps).shape == (R, 4 + accounting.N_ORDERS)
    ref = fleet_round_telemetry(proto, TJ.replicate_major(out_on["chan"]),
                                TJ.replicate_major(out_on["W"]),
                                spec=tele)                       # [R, T]
    for name, refcol in ref.items():
        got = rows[:, :, tele.fields.index(name)].T              # [R, T]
        np.testing.assert_allclose(got, np.asarray(refcol), rtol=1e-5,
                                   err_msg=name)
    np.testing.assert_allclose(
        np.asarray(c_on.eps)[:, 0],
        np.asarray(ref["epsilon"]).sum(axis=1), rtol=1e-5)
    # per-replicate RDP ledger == host recompute on the [R, T] channel log
    from repro.obs import telemetry as tl
    rdp_ref = jax.vmap(jax.vmap(
        lambda ch, w: tl.rdp_round(proto, ch, w)))(
        TJ.replicate_major(out_on["chan"]), TJ.replicate_major(out_on["W"]))
    np.testing.assert_allclose(np.asarray(c_on.eps)[:, 4:],
                               np.asarray(rdp_ref).sum(axis=1), rtol=1e-4)


def test_telemetry_field_subset_layout():
    """A partial spec emits exactly its enabled columns, in catalogue
    order, and no eps accumulator is required when epsilon is off."""
    from repro import obs
    cfg, proto, flat, unravel_row = _flat_setup({})
    tele = obs.TelemetrySpec(grad_norm=False, snr_db=False, epsilon=False)
    assert tele.fields == ("loss", "consensus", "deep_fade",
                           "participation")
    body = TJ.make_round_body(cfg, proto, _store(),
                              unravel_row=unravel_row, telemetry=tele)
    carry, out = TJ.ChunkRunner(body, donate=False).run(
        TJ.TrajCarry(jax.random.PRNGKey(17), flat), 3)
    assert np.asarray(out["telemetry"]).shape == (3, 4)
    assert carry.eps is None


def test_lm_round_body_runs():
    """The LM-family scan body (tokens batches) compiles and steps."""
    from repro.configs.registry import get_arch
    cfg = get_arch("dwfl-paper").replace(
        family="transformer", d_model=16, num_layers=1, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=11)
    toks = (np.arange(6000) % 11).astype(np.int32)
    store = LMStore.build(toks, W, 2, 8)
    proto = _proto()
    key = jax.random.PRNGKey(10)
    wp, spec = _flat(P.init_worker_params(key, cfg, W))
    body = TJ.make_round_body(cfg, proto, store, spec=spec)
    runner = TJ.ChunkRunner(body, donate=False)
    carry, out = runner.run(TJ.TrajCarry(key, wp), 3)
    assert out["metrics"]["loss"].shape == (3,)
    assert np.isfinite(np.asarray(out["metrics"]["loss"])).all()
