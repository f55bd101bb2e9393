"""Model-level correctness: decode-vs-parallel consistency, sliding window,
M-RoPE, recurrent state semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models import model as M
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import xlstm as X


def _prefill_then_decode_logits(cfg, key, S_len=24, extra=4):
    """Run prefill on S tokens then decode `extra` more; compare each decoded
    logit against the full parallel forward over the whole sequence."""
    params = M.init_params(key, cfg)
    B = 2
    toks = jax.random.randint(key, (B, S_len + extra), 0, cfg.vocab_size)
    full_logits, _, _ = M.forward(params, {"tokens": toks}, cfg, mode="train")

    _, cache = M.prefill(params, {"tokens": toks[:, :S_len]}, cfg)
    big = M.init_cache(cfg, B, S_len + extra)
    def splice(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        sl = tuple(slice(0, s) for s in src.shape)
        return dst.at[sl].set(src.astype(dst.dtype))
    cache = jax.tree_util.tree_map(splice, big, cache)

    outs = []
    for i in range(extra):
        lg, cache = M.decode_step(params, {"tokens": toks[:, S_len + i:S_len + i + 1]},
                                  cache, S_len + i, cfg)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)                      # [B, extra, V]
    want = full_logits[:, S_len:S_len + extra]
    return got, want


@pytest.mark.parametrize("arch", ["olmo-1b", "gemma-2b", "glm4-9b", "qwen2-72b"])
def test_decode_matches_parallel_dense(arch):
    cfg = get_arch(arch).reduced()
    got, want = _prefill_then_decode_logits(cfg, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_decode_matches_parallel_moe():
    cfg = get_arch("deepseek-moe-16b").reduced()
    got, want = _prefill_then_decode_logits(cfg, jax.random.PRNGKey(1))
    # routing drops no token, so batched prefill and per-token decode
    # route alike
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


def test_decode_matches_parallel_ssm():
    cfg = get_arch("zamba2-7b").reduced()
    got, want = _prefill_then_decode_logits(cfg, jax.random.PRNGKey(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


def test_decode_matches_parallel_xlstm():
    cfg = get_arch("xlstm-1.3b").reduced()
    got, want = _prefill_then_decode_logits(cfg, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-2, atol=1e-2)


def test_sliding_window_restricts_attention():
    """With a window of w, token t must be unaffected by tokens < t - w."""
    cfg = get_arch("gemma-2b").reduced(sliding_window=8, num_layers=1)
    key = jax.random.PRNGKey(4)
    params = M.init_params(key, cfg)
    toks = jax.random.randint(key, (1, 32), 0, cfg.vocab_size)
    lg1, _, _ = M.forward(params, {"tokens": toks}, cfg, mode="train")
    # perturb token 0: logits at positions > 8 must be unchanged
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab_size)
    lg2, _, _ = M.forward(params, {"tokens": toks2}, cfg, mode="train")
    np.testing.assert_allclose(np.asarray(lg1[0, 10:]), np.asarray(lg2[0, 10:]),
                               rtol=1e-4, atol=1e-5)
    # ...but position 1 (inside the window) does change
    assert float(jnp.max(jnp.abs(lg1[0, 1] - lg2[0, 1]))) > 1e-4


def test_mrope_collapses_to_rope_for_text():
    """Equal (t,h,w) position ids must reproduce plain RoPE."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, 16, 4, 64))
    pos = jnp.arange(16)[None].repeat(2, 0)
    plain = L.apply_rope(x, pos, 10000.0)
    thw = jnp.stack([pos, pos, pos], 0)
    mr = L.apply_mrope(x, thw, 10000.0, (16, 24, 24))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(mr),
                               rtol=1e-5, atol=1e-5)


def test_mrope_distinguishes_spatial_positions():
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (1, 4, 2, 64))
    t = jnp.zeros((1, 4), jnp.int32)
    h1 = jnp.array([[0, 1, 2, 3]])
    w1 = jnp.zeros((1, 4), jnp.int32)
    a = L.apply_mrope(x, jnp.stack([t, h1, w1]), 1e4, (16, 24, 24))
    b = L.apply_mrope(x, jnp.stack([t, w1, h1]), 1e4, (16, 24, 24))
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


def test_ssd_decode_matches_chunked_tail():
    """Feeding tokens one-by-one through the recurrent step reproduces the
    chunked scan exactly (state-space duality)."""
    B, S_, H, P, N = 1, 32, 4, 8, 8
    key = jax.random.PRNGKey(7)
    xh = jax.random.normal(key, (B, S_, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S_, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S_, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S_, N)) * 0.3
    y_par, s_par = S.ssd_chunked(xh, dt, A, Bm, Cm, chunk=8)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S_):
        y1, state = S.ssd_decode_step(xh[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], state)
        ys.append(y1)
    y_seq = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_par), np.asarray(state),
                               rtol=1e-4, atol=1e-5)


def test_mlstm_decode_matches_chunked():
    B, S_, H, dk, dv = 1, 32, 2, 8, 16
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (B, S_, H, dk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S_, H, dk)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S_, H, dv)) * 0.5
    i_raw = jax.random.normal(jax.random.fold_in(key, 3), (B, S_, H))
    f_raw = jax.random.normal(jax.random.fold_in(key, 4), (B, S_, H)) + 2.0
    h_par, _ = X._mlstm_chunked(q, k, v, i_raw, f_raw, chunk=8)
    state = (jnp.zeros((B, H, dk, dv)), jnp.zeros((B, H, dk)),
             jnp.full((B, H), -jnp.inf))
    hs = []
    for t in range(S_):
        h1, state = X.mlstm_decode_step(q[:, t], k[:, t], v[:, t],
                                        i_raw[:, t], f_raw[:, t], state)
        hs.append(h1)
    h_seq = jnp.stack(hs, 1)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_seq),
                               rtol=2e-3, atol=2e-3)


def test_nonparametric_ln_has_no_params():
    cfg = get_arch("olmo-1b").reduced()
    p = L.norm_init(cfg, jnp.float32)
    assert p == {}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, cfg.d_model))
    y = L.norm_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.var(y, -1)), 1.0, atol=1e-3)


def _dense_experts(params, x, cfg):
    """Every expert on every token, [T, E, d] (held experts only)."""
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, params["w_gate"],
                               precision="highest"))
    h = h * jnp.einsum("td,edf->tef", x, params["w_up"], precision="highest")
    return jnp.einsum("tef,efd->ted", h, params["w_down"], precision="highest")


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_routing_drops_no_token_and_balances(norm_topk):
    """A router that sends every token to the same two experts: each token
    still gets its top-k experts' gated sum (a capacity would drop most of
    them), gates sum to 1 only under norm_topk_prob, and the sequence-wise
    balance loss is E * sum_i f_i P_i."""
    from repro.models import moe as Mo
    cfg = get_arch("qwen3-moe-235b-a22b").reduced(norm_topk_prob=norm_topk)
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    params = Mo.moe_init(jax.random.PRNGKey(9), cfg, jnp.float32)
    bias = jnp.zeros((cfg.d_model, E)).at[:, :2].set(0.5 / cfg.d_model)
    params["router"] = params["router"] * 0.01 + bias
    B, S_ = 2, 64
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (B, S_, cfg.d_model)))
    y, aux = Mo.routed(params, x, cfg)

    probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ params["router"], -1)
    gates, idx = jax.lax.top_k(probs, k)
    assert bool(jnp.all(jnp.sort(idx, -1) == jnp.arange(2)))   # all on 0, 1
    if norm_topk:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    assert bool(jnp.all(jnp.sum(gates, -1) < 1.0)) != norm_topk
    dense = _dense_experts(params, x.reshape(-1, cfg.d_model), cfg)
    want = jnp.sum(jnp.take_along_axis(dense, idx[..., None], axis=1)
                   * gates[..., None], axis=1)
    # float32 on the CPU: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    p = probs.reshape(B, S_, E)
    f = jnp.sum(jax.nn.one_hot(idx.reshape(B, S_, k), E), axis=(1, 2)) * (
        E / (S_ * k))
    np.testing.assert_allclose(float(aux), float(jnp.mean(jnp.sum(
        f * jnp.mean(p, axis=1), -1))), rtol=1e-6)
    assert float(aux) > 1.0      # unbalanced: a balanced router reads ~1