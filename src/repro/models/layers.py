"""Foundational layers: norms, rotary embeddings, attention, MLPs.

Pure-functional style: ``init_*`` builds a param pytree (plain dicts),
``*_apply`` consumes it. No framework dependency — params are directly the
objects the DWFL protocol perturbs and exchanges.

Attention uses a block-chunked streaming-softmax formulation for long
sequences (exact causal FLOPs: the outer query-block loop is a Python loop
so each block's KV extent is static), a plain einsum path for short
sequences, and a single-query cache path for decode. The Pallas
flash-attention kernel (repro.kernels.flash_attention) is the TPU-optimized
equivalent of the chunked path and is validated against it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def stacked(keys, fn):
    """Initialize a stack of identical layers: returns pytree with leading L axis."""
    return jax.vmap(fn)(keys)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, dtype):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm_type == "nonparametric_ln":  # olmo: no affine params
        return {}
    raise ValueError(cfg.norm_type)


def norm_apply(params, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        y = y * params["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if cfg.norm_type == "layernorm":
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    half = x.shape[-1] // 2
    freqs = _rope_freqs(x.shape[-1], theta)  # [half]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    ang = ang[..., None, :]  # broadcast over heads: [..., S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections_for(head_dim: int, sections: Tuple[int, ...]) -> Tuple[int, ...]:
    """Scale the (t,h,w) section split to this head_dim's half-dim."""
    half = head_dim // 2
    total = sum(sections)
    scaled = [max(1, (s * half) // total) for s in sections]
    scaled[0] += half - sum(scaled)
    return tuple(scaled)


def apply_mrope(x, positions_thw, theta: float, sections: Tuple[int, ...]):
    """qwen2-vl M-RoPE. positions_thw: [3, ..., S] (temporal, height, width ids).

    Each rotary half-dim is assigned to one of the three position streams
    according to ``sections``; text tokens carry identical t==h==w ids, which
    makes M-RoPE collapse to ordinary RoPE for pure-text input.
    """
    half = x.shape[-1] // 2
    secs = mrope_sections_for(x.shape[-1], sections)
    freqs = _rope_freqs(x.shape[-1], theta)  # [half]
    # Build a per-half-dim position tensor by selecting the stream per section.
    stream_id = jnp.repeat(jnp.arange(3), jnp.array(secs), total_repeat_length=half)  # [half]
    # positions_thw: [3, ..., S] -> pos_per_dim [..., S, half]
    pos = jnp.moveaxis(positions_thw, 0, -1)  # [..., S, 3]
    idx = jnp.broadcast_to(stream_id, pos.shape[:-1] + (half,))
    pos_per_dim = jnp.take_along_axis(pos.astype(jnp.float32), idx, axis=-1)
    ang = pos_per_dim * freqs  # [..., S, half]
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig, dtype):
    if cfg.kv_lora_rank:
        from repro.models import mla
        return mla.init(key, cfg, dtype)
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def _project_qkv(params, x, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    B, S = x.shape[0], x.shape[1]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    return q, k, v


def _rotate(q, k, cfg: ModelConfig, positions):
    if cfg.use_mrope:
        # positions: [3, B, S]
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif not cfg.learned_pos_emb:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _gqa_scores(q, k):
    """q: [B,Sq,H,hd], k: [B,Sk,Hkv,hd] -> scores [B,H,Sq,Sk] with GQA groups."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)
    return s.reshape(B, Hkv * G, Sq, k.shape[1])


def _gqa_out(probs, v, H):
    """probs: [B,H,Sq,Sk], v: [B,Sk,Hkv,hd] -> [B,Sq,H,hd]."""
    B, _, Sq, Sk = probs.shape
    Hkv = v.shape[2]
    G = H // Hkv
    pg = probs.reshape(B, Hkv, G, Sq, Sk)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pg, v)
    return o.reshape(B, Sq, H, v.shape[-1])


def _plain_causal_attention(q, k, v, cfg: ModelConfig, q_offset=0,
                            scale=None):
    """``scale`` multiplies the scores (default 1/sqrt(head dim))."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    scores = _gqa_scores(q, k) * scale  # [B,H,Sq,Sk]
    Sq, Sk = scores.shape[-2], scores.shape[-1]
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = kpos[None, :] <= qpos[:, None]
    if cfg.sliding_window is not None:
        mask &= kpos[None, :] > qpos[:, None] - cfg.sliding_window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return _gqa_out(probs, v, cfg.num_heads)


def _chunked_causal_attention(q, k, v, cfg: ModelConfig, q_block: int = 1024,
                              scale=None):
    """Memory-efficient exact-causal attention.

    Outer query-block loop is a Python loop (static), so block i attends only
    to KV[0 : (i+1)*q_block] — exact causal FLOPs, O(S * q_block) live scores.
    With a sliding window, each block attends only to its window extent.
    """
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    n_blocks = S // q_block
    assert n_blocks * q_block == S, (S, q_block)
    outs = []
    for i in range(n_blocks):
        qs = q[:, i * q_block:(i + 1) * q_block]
        lo = 0
        if cfg.sliding_window is not None:
            lo = max(0, (i + 1) * q_block - cfg.sliding_window - q_block)
        hi = (i + 1) * q_block
        ks, vs = k[:, lo:hi], v[:, lo:hi]
        scores = _gqa_scores(qs, ks) * scale
        qpos = jnp.arange(q_block) + i * q_block
        kpos = jnp.arange(lo, hi)
        mask = kpos[None, :] <= qpos[:, None]
        if cfg.sliding_window is not None:
            mask &= kpos[None, :] > qpos[:, None] - cfg.sliding_window
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        outs.append(_gqa_out(probs, vs, cfg.num_heads))
    return jnp.concatenate(outs, axis=1)


def _decode_attention(q, k_cache, v_cache, cache_len, cfg: ModelConfig, window_pos=None):
    """Single-token attention against a cache.

    q: [B,1,H,hd]; caches: [B,Smax,Hkv,hd]; cache_len: scalar count of valid
    entries (the new token's k/v must already be written). ``window_pos``
    (ring-buffer caches): absolute position per cache slot, for masking.
    """
    hd = q.shape[-1]
    scores = _gqa_scores(q, k_cache) / math.sqrt(hd)  # [B,H,1,Smax]
    slot = jnp.arange(k_cache.shape[1])
    if window_pos is None:
        valid = slot < cache_len
    else:
        valid = window_pos >= 0  # ring cache: slots hold absolute pos or -1
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return _gqa_out(probs, v_cache, cfg.num_heads)


def attention_apply(
    params,
    x,
    cfg: ModelConfig,
    positions,
    *,
    mode: str,
    cache: Optional[dict] = None,
    cache_index=None,
    use_pallas: bool = False,
):
    """mode: 'train' | 'prefill' | 'decode'.

    prefill additionally returns the filled KV cache; decode consumes/returns
    the cache (functional update). Latent attention (``kv_lora_rank`` > 0,
    repro.models.mla) runs in training mode only.
    """
    if cfg.kv_lora_rank:
        from repro.models import mla
        return mla.apply(params, x, cfg, positions, mode=mode), None
    B, S = x.shape[0], x.shape[1]
    q, k, v = _project_qkv(params, x, cfg)
    q, k = _rotate(q, k, cfg, positions)

    new_cache = None
    if mode == "decode":
        assert cache is not None and S == 1
        if "pos" in cache:  # ring buffer (sliding window)
            W = cache["k"].shape[1]
            slot = jnp.mod(cache_index, W)
            k_cache = cache["k"].at[:, slot].set(k[:, 0])
            v_cache = cache["v"].at[:, slot].set(v[:, 0])
            pos = cache["pos"].at[slot].set(cache_index)
            o = _decode_attention(q, k_cache, v_cache, cache_index + 1, cfg, window_pos=pos)
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos}
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, axis=1)
            o = _decode_attention(q, k_cache, v_cache, cache_index + 1, cfg)
            new_cache = {"k": k_cache, "v": v_cache}
    else:
        if use_pallas:
            from repro.kernels.flash_attention import ops as fa_ops
            o = fa_ops.flash_attention(q, k, v, causal=True,
                                       sliding_window=cfg.sliding_window)
        elif S > 1024:
            o = _chunked_causal_attention(q, k, v, cfg)
        else:
            o = _plain_causal_attention(q, k, v, cfg)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}

    y = o.reshape(B, S, -1) @ params["wo"]
    return y, new_cache


def cross_attention_init(key, cfg: ModelConfig, dtype):
    return attention_init(key, cfg.replace(qkv_bias=False), dtype)


def cross_attention_apply(params, x, enc_out, cfg: ModelConfig):
    """Encoder-decoder cross attention (whisper). No causal mask, no rope."""
    hd = cfg.resolved_head_dim
    B, S = x.shape[0], x.shape[1]
    Se = enc_out.shape[1]
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (enc_out @ params["wk"]).reshape(B, Se, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(B, Se, cfg.num_kv_heads, hd)
    scores = _gqa_scores(q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    o = _gqa_out(probs, v, cfg.num_heads)
    return o.reshape(B, S, -1) @ params["wo"]


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d_ff = d_ff if d_ff is not None else cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, cfg.d_model, d_ff, dtype),
            "w_up": dense_init(k2, cfg.d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, cfg.d_model, dtype),
        }
    return {  # plain gelu MLP (whisper)
        "w_up": dense_init(k1, cfg.d_model, d_ff, dtype),
        "w_down": dense_init(k2, d_ff, cfg.d_model, dtype),
    }


def mlp_apply(params, x, cfg: ModelConfig):
    if cfg.mlp_type == "swiglu":
        return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
    if cfg.mlp_type == "geglu":
        return (jax.nn.gelu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
    return jax.nn.gelu(x @ params["w_up"]) @ params["w_down"]


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def embed_init(key, cfg: ModelConfig, dtype):
    p = {"tok": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(key, 1), cfg.d_model, cfg.vocab_size, dtype)
    if cfg.learned_pos_emb:
        max_pos = 32768 if not cfg.is_encoder_decoder else 65536
        p["pos"] = (jax.random.normal(jax.random.fold_in(key, 2),
                                      (max_pos, cfg.d_model)) * 0.02).astype(dtype)
    return p


def embed_apply(params, tokens, cfg: ModelConfig):
    x = params["tok"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed_apply(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return x @ params["tok"].T
    return x @ params["unembed"]
