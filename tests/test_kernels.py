"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# dp_mix (fused flat-buffer DWFL round)
# ---------------------------------------------------------------------------

from repro.core import dwfl as _dwfl
from repro.core import exchange as _X
from repro.core.channel import ChannelConfig as _CC
from repro.kernels.dp_mix import ops as mix_ops
from repro.kernels.dp_mix import ref as mix_ref


def _mix_setup(N=6, d=2000, seed=3):
    chan = _CC(n_workers=N, p_dbm=30.0, sigma=0.7, sigma_m=0.4,
               seed=seed).realize()
    key = jax.random.PRNGKey(seed)
    p = jax.random.normal(key, (N, d))
    g = jax.random.normal(jax.random.fold_in(key, 1), (N, d)) * 0.2
    return chan, p, g, _X.plan_complete(None, chan)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_dp_mix_deterministic_matches_matrix_reference(impl):
    """σ = σ_m = 0: both implementations reduce to the exact Eqt. (8)
    mixing X ← (X − γG)Ψ (f32 tolerance vs the oracle)."""
    N, d = 6, 500
    chan, p, g, plan = _mix_setup(N, d)
    gamma, eta = 0.1, 0.45
    out = mix_ops.dp_mix_round(p, g, 7, plan.W, 0.0 * plan.amp, plan.c, 0.0,
                               gamma=gamma, eta=eta, m_scale=plan.m_scale,
                               impl=impl)
    want = _dwfl.matrix_form_reference(
        np.asarray(p), np.asarray(g), np.zeros((N, d)), np.zeros((N, d)),
        chan, gamma, eta)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("N,d", [(2, 1), (3, 129), (10, 16_385),
                                 (16, 40_000)])
def test_dp_mix_ragged_shapes_match_reference(N, d):
    """Shapes whose last column tile is ragged and whose worker rows pad
    to the sublane multiple: on the deterministic path both lowerings
    match ref.py, and with noise they draw the same fields into every
    real row (equal to f32 rounding: the mix sums each tile as wide as
    its lowering cuts it)."""
    chan, p, g, plan = _mix_setup(N, d)
    kw = dict(gamma=0.05, eta=0.4, m_scale=plan.m_scale)
    want = np.asarray(mix_ref.dp_mix_round_ref(
        p, g, KEY, plan.W, 0.0 * plan.amp, plan.c, 0.0, **kw))
    noisy = {}
    for impl in ("jnp", "pallas_interpret"):
        det = mix_ops.dp_mix_round(p, g, 7, plan.W, 0.0 * plan.amp, plan.c,
                                   0.0, impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(det), want, rtol=1e-5,
                                   atol=1e-6)
        noisy[impl] = np.asarray(mix_ops.dp_mix_round(
            p, g, 7, plan.W, plan.amp, plan.c, chan.awgn_sigma, impl=impl,
            **kw))
        assert noisy[impl].shape == (N, d)
        assert np.all(np.isfinite(noisy[impl]))
    np.testing.assert_allclose(noisy["jnp"], noisy["pallas_interpret"],
                               rtol=1e-6, atol=1e-6)
    assert np.all(np.abs(noisy["jnp"] - want).max(axis=1) > 1e-3)


def test_dp_mix_jnp_lowering_bitwise_matches_interpret():
    """The CPU (fused-jnp) lowering and the interpret-mode Pallas kernel
    draw IDENTICAL noise (same counter-hash, same index map) and compute
    identical arithmetic — bitwise-equal outputs."""
    chan, p, g, plan = _mix_setup()
    a = mix_ops.dp_mix_round_plan(p, g, 7, plan, gamma=0.05, eta=0.4,
                                  impl="jnp")
    b = mix_ops.dp_mix_round_plan(p, g, 7, plan, gamma=0.05, eta=0.4,
                                  impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_mix_noise_moments():
    """Stochastic path vs the per-receiver variance of the unified update:
    Var_i = η²[Σ_{k≠i} W_ik²·amp_k² + amp_i²]/c² + η²·m_scale_i²·σ_m²
    (complete graph, W_ii = 0), plus agreement with ref.py's moments."""
    N, d = 6, 60_000
    chan, p, g, plan = _mix_setup(N, d)
    gamma, eta = 0.1, 0.45
    det = mix_ops.dp_mix_round(p, g, 7, plan.W, 0.0 * plan.amp, plan.c, 0.0,
                               gamma=gamma, eta=eta, m_scale=plan.m_scale)
    out = mix_ops.dp_mix_round(p, g, 7, plan.W, plan.amp, plan.c,
                               chan.awgn_sigma, gamma=gamma, eta=eta,
                               m_scale=plan.m_scale)
    outr = mix_ref.dp_mix_round_ref(p, g, KEY, plan.W, plan.amp, plan.c,
                                    chan.awgn_sigma, gamma=gamma, eta=eta,
                                    m_scale=plan.m_scale)
    amp = np.asarray(plan.amp, np.float64)
    Wm = np.asarray(plan.W, np.float64)
    c = float(chan.c)
    ms = np.asarray(plan.m_scale, np.float64)
    var = np.array([
        eta ** 2 * ((Wm[i] ** 2 * amp ** 2).sum() + amp[i] ** 2) / c ** 2
        + eta ** 2 * ms[i] ** 2 * chan.cfg.sigma_m ** 2 for i in range(N)])
    for o in (out, outr):
        resid = np.asarray(o, np.float64) - np.asarray(det, np.float64)
        ratio = resid.std(axis=1) / np.sqrt(var)
        np.testing.assert_allclose(ratio, 1.0, atol=0.04)
        assert np.abs(resid.mean(axis=1)).max() < 5 * np.sqrt(var.max() / d)


def test_dp_mix_seed_sensitivity_and_dtype():
    """Different seeds → different noise; bf16 buffer in → bf16 out (the
    kernel's dtype contract)."""
    chan, p, g, plan = _mix_setup()
    a = mix_ops.dp_mix_round_plan(p, g, 7, plan, gamma=0.05, eta=0.4)
    b = mix_ops.dp_mix_round_plan(p, g, 8, plan, gamma=0.05, eta=0.4)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    pb = p.astype(jnp.bfloat16)
    gb = g.astype(jnp.bfloat16)
    ob = mix_ops.dp_mix_round_plan(pb, gb, 7, plan, gamma=0.05, eta=0.4)
    assert ob.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ob, np.float32), np.asarray(a),
                               atol=0.15)


def test_dp_mix_gossip_noiseless_path():
    """noisy=False (gossip plan): pure mixing, no PRNG work, mean exactly
    preserved."""
    chan, p, g, plan = _mix_setup()
    gplan = _X.plan_gossip(None, chan)
    out = mix_ops.dp_mix_round_plan(p, g, 7, gplan, gamma=0.05, eta=0.5)
    x = p - 0.05 * g
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(x.mean(0)), rtol=1e-5, atol=1e-6)
    want = x + 0.5 * (jnp.asarray(gplan.W) @ x - x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("Np,d,noisy,counter_width,block_d", [
    # at the VMEM ceiling the tile is the 512 columns it was measured at
    (1024, 855_050, True, None, 512), (1504, 855_050, False, None, 512),
    # sharded windows: the widest multiple of 128 within the bound that
    # divides the window
    (1024, 4 * 128 * 3, True, 4 * 128 * 12, 512),
    (1024, 2 * 128 * 3, True, 2 * 128 * 12, 384),
    (1024, 128 * 3, True, 128 * 12, 384),
    (16, 213_760, True, 855_168, 1280),        # the paper's 4-way shard
    # the paper's buffer (N = 10): the widest tile, noisy or gossip
    (16, 855_050, True, None, 16384), (16, 855_050, False, None, 16384),
    # constant area below the ceiling; never wider than the buffer
    (512, 855_050, True, None, 1024), (16, 300, True, None, 384)])
def test_dp_mix_tile_width_from_vmem_ceiling(Np, d, noisy, counter_width,
                                            block_d):
    """The TPU tile holds at most the area of the ceiling's (MAX_NP, 512)
    tile (dp_mix.MAX_NP is measured at 512 columns), up to BLOCK_D_CAP
    columns, narrowed for a sharded window only as far as it takes to
    divide the window."""
    from repro.kernels.dp_mix import dp_mix as K
    assert K.BLOCK_D_CAP == 16384
    assert mix_ops._pallas_block_d(Np, d, noisy, counter_width) == block_d


@pytest.mark.parametrize("N,noisy,block_d", [
    (1025, True, None), (1505, False, None), (1025, True, 128)])
def test_dp_mix_refuses_dense_past_vmem_ceiling(N, noisy, block_d):
    """Past the ceiling the Pallas round raises (naming the sparse path)
    instead of handing the TPU compiler a kernel it runs out of VMEM on,
    whatever tile it is asked for."""
    p = jnp.zeros((N, 256), jnp.float32)
    with pytest.raises(ValueError, match="sparse-neighbors"):
        mix_ops.dp_mix_round(p, p, 0, jnp.eye(N), jnp.ones(N), 1.0, 0.0,
                             gamma=0.0, eta=0.5, noisy=noisy, impl="pallas",
                             block_d=block_d)


def test_dp_mix_vmapped_kernel_matches_per_replicate():
    """Under vmap (the fleet's replicate axis, nested or with a shared
    operand) the kernel takes the replicates as its own grid axis and
    reproduces each replicate's own call bitwise."""
    R, N, d = 3, 5, 300
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    p = jax.random.normal(k[0], (R, N, d))
    g = jax.random.normal(k[1], (R, N, d))
    W = jax.random.uniform(k[2], (R, N, N))
    W = W / W.sum(-1, keepdims=True)
    amp = jnp.linspace(0.5, 1.5, R * N).reshape(R, N)
    c, sm = jnp.array([1.0, 2.0, 3.0]), jnp.array([0.1, 0.3, 0.5])
    seed = jnp.arange(R, dtype=jnp.int32) + 7

    def f(p, g, s, W, a, c, sm):
        return mix_ops.dp_mix_round(p, g, s, W, a, c, sm, gamma=0.1, eta=0.4,
                                    impl="pallas_interpret", block_d=128)
    want = np.stack([np.asarray(f(p[r], g[r], seed[r], W[r], amp[r], c[r],
                                  sm[r])) for r in range(R)])
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(f)(p, g, seed, W, amp, c, sm)), want)
    nested = jax.vmap(jax.vmap(f))(p[None], g[None], seed[None], W[None],
                                   amp[None], c[None], sm[None])
    np.testing.assert_array_equal(np.asarray(nested[0]), want)
    shared = jax.vmap(f, in_axes=(0, 0, None, 0, 0, 0, 0))(
        p, g, seed[0], W, amp, c, sm)
    np.testing.assert_array_equal(
        np.asarray(shared[1]),
        np.asarray(f(p[1], g[1], seed[0], W[1], amp[1], c[1], sm[1])))


@pytest.mark.parametrize("noisy", [True, False], ids=["noisy", "gossip"])
def test_dp_mix_unpadded_kernel_matches_jnp_windows(noisy):
    """The Pallas path reads the [N, d] buffer as it is (N = 10 is no
    sublane multiple; d = 1000 leaves a ragged last tile of 232 of 256
    columns). Each column tile draws the jnp lowering's counters (stride
    roundup(d, 128)) and computes its arithmetic: bitwise the jnp
    lowering of that window (``col0``/``counter_width``, the shard
    hooks), alone and under vmap with R = 3 replicates."""
    N, d, bd = 10, 1000, 256
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    R = 3
    p = jax.random.normal(k[0], (R, N, d))
    g = jax.random.normal(k[1], (R, N, d))
    W = jax.random.uniform(k[2], (R, N, N))
    W = W / W.sum(-1, keepdims=True)
    amp = jnp.linspace(0.5, 1.5, R * N).reshape(R, N)
    c, sm = jnp.array([1.0, 2.0, 3.0]), jnp.array([0.1, 0.3, 0.5])
    seed = jnp.arange(R, dtype=jnp.int32) + 7
    kw = dict(gamma=0.1, eta=0.4, noisy=noisy)

    def kernel(p, g, s, W, a, c, sm):
        return mix_ops.dp_mix_round(p, g, s, W, a, c, sm, block_d=bd,
                                    impl="pallas_interpret", **kw)

    def jnp_windows(p, g, s, W, a, c, sm):
        return np.concatenate([np.asarray(mix_ops.dp_mix_round(
            p[:, j:j + bd], g[:, j:j + bd], s, W, a, c, sm, impl="jnp",
            block_d=bd, col0=j, counter_width=1024, **kw))
            for j in range(0, d, bd)], axis=1)

    want = np.stack([jnp_windows(p[r], g[r], seed[r], W[r], amp[r], c[r],
                                 sm[r]) for r in range(R)])
    assert want.shape == (R, N, d)
    one = kernel(p[0], g[0], seed[0], W[0], amp[0], c[0], sm[0])
    assert one.shape == (N, d)
    np.testing.assert_array_equal(np.asarray(one), want[0])
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(kernel)(p, g, seed, W, amp, c, sm)), want)


def test_dp_mix_sparse_plan_refuses_pallas():
    """The sparse round is an XLA gather on every backend: a Pallas
    request is refused, not silently run as the gather."""
    import dataclasses
    from repro.net.sparse import sparsify_dense
    chan, p, g, plan = _mix_setup()
    splan = dataclasses.replace(plan, W=sparsify_dense(plan.W, 3))
    with pytest.raises(NotImplementedError, match="XLA gather"):
        mix_ops.dp_mix_round_plan(p, g, 7, splan, gamma=0.05, eta=0.4,
                                  impl="pallas")
    assert mix_ops.lowering(sparse=True) == "xla-gather"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Hkv,hd,win", [
    (2, 256, 4, 2, 64, None),
    (1, 256, 4, 1, 64, 96),     # MQA + sliding window
    (2, 128, 2, 2, 32, None),
    (1, 512, 8, 4, 64, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Hkv, hd, win, dtype):
    q = jax.random.normal(KEY, (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, hd)).astype(dtype)
    got = fa_ops.flash_attention(q, k, v, causal=True, sliding_window=win,
                                 block_q=64, block_k=64)
    kr = jnp.repeat(k, H // Hkv, 2)
    vr = jnp.repeat(v, H // Hkv, 2)
    want = fa_ref.attention_ref(q, kr, vr, causal=True, sliding_window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_model_layer():
    """The kernel path and the model's chunked-jnp path agree."""
    from repro.configs.registry import get_arch
    from repro.models import layers as L
    cfg = get_arch("glm4-9b").reduced(num_layers=1)
    key = KEY
    p = L.attention_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 256, cfg.d_model)) * 0.1
    pos = jnp.arange(256)[None].repeat(2, 0)
    y1, _ = L.attention_apply(p, x, cfg, pos, mode="train", use_pallas=False)
    y2, _ = L.attention_apply(p, x, cfg, pos, mode="train", use_pallas=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 8, 16, 16, 32),
    (1, 256, 16, 32, 64, 64),
    (2, 64, 8, 64, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    xh = (jax.random.normal(KEY, (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    Bm = (jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, N)) * 0.3).astype(dtype)
    y1, s1 = ssd_ops.ssd_scan(xh, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-5)


def test_ssd_chunk_invariance():
    """The chunk size is an implementation detail — results must not
    depend on it (chunked scan correctness)."""
    B, S, H, P, N = 1, 128, 4, 16, 16
    xh = jax.random.normal(KEY, (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, N)) * 0.3
    y32, s32 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=32)
    y128, s128 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s32), np.asarray(s128),
                               rtol=1e-4, atol=1e-5)
