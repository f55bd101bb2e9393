"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), training
mode, with YaRN rope scaling.

Without a query compression (``q_lora_rank`` null, as in DeepSeek-V2-Lite):

    q                = x · W_q            per head: q_nope | q_rope
    c_kv | k_rope    = x · W_kv_a         widths kv_lora_rank | rope dim
    k_nope | v       = RMSNorm(c_kv) · W_kv_b   per head
    scores           = (q_nope·k_nope + rope(q_rope)·rope(k_rope)) · s

``k_rope`` is one rotated key shared by every head. The causal softmax runs
in float32 and the output is ``softmax · v → W_o``. The rope half uses the
split-half channel order of ``layers.apply_rope`` (the published code
interleaves; with seeded random weights the two differ by a fixed
permutation of ``W_q``'s and ``W_kv_a``'s rope columns). The serving cache
of the compressed latent is not built: prefill and decode raise.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor g(f, mu) = 0.1 mu ln f + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _correction_dim(rotations: float, dim: int, theta: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_inv_freq(cfg: ModelConfig):
    """[rope dim / 2] inverse frequencies: plain rope, or YaRN's blend of
    interpolated (f / factor) and extrapolated (f) frequencies over the
    ramp between the beta_fast and beta_slow correction dims."""
    dim, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1:
        return extra
    lo = max(math.floor(_correction_dim(cfg.rope_beta_fast, dim, theta,
                                        cfg.rope_original_max_pos)), 0)
    hi = min(math.ceil(_correction_dim(cfg.rope_beta_slow, dim, theta,
                                       cfg.rope_original_max_pos)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp            # share of the extrapolated frequency
    return extra / cfg.rope_factor * (1.0 - keep) + extra * keep


def score_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q head dim), times YaRN's g(factor, mscale_all_dim)^2."""
    s = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.rope_factor > 1 and cfg.rope_mscale_all_dim:
        s *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return s


def _rope(x, positions, inv_freq, mscale):
    """Split-half rotation of x [..., S, H, dim] at positions [..., S],
    cos and sin scaled by ``mscale``."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def init(key, cfg: ModelConfig, dtype):
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(kq, cfg.d_model, H * (nope + rope), dtype),
        "wkv_a": L.dense_init(ka, cfg.d_model, r + rope, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": L.dense_init(kb, r, H * (nope + vd), dtype),
        "wo": L.dense_init(ko, H * vd, cfg.d_model, dtype),
    }


def apply(params, x, cfg: ModelConfig, positions, *, mode: str):
    """x: [B, S, d] -> [B, S, d], causal self-attention (train mode)."""
    if mode != "train":
        raise NotImplementedError(
            "latent attention runs in training mode only: the serving "
            "cache of the compressed latent is not built")
    with jax.named_scope("dwfl.grad.mla"):
        B, S = x.shape[0], x.shape[1]
        H, r = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        q = (x @ params["wq"]).reshape(B, S, H, nope + rope)
        kv_a = x @ params["wkv_a"]
        c_kv = L.norm_apply({"scale": params["kv_norm"]}, kv_a[..., :r], cfg)
        kv = (c_kv @ params["wkv_b"]).reshape(B, S, H, nope + vd)
        inv_freq = rope_inv_freq(cfg)
        m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
        q_rope = _rope(q[..., nope:], positions, inv_freq, m)
        k_rope = _rope(kv_a[..., None, r:], positions, inv_freq, m)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))],
            axis=-1)
        v = kv[..., nope:]
        att = (L._chunked_causal_attention if S > 1024
               else L._plain_causal_attention)
        o = att(q, k, v, cfg, scale=score_scale(cfg))
        return o.reshape(B, S, H * vd) @ params["wo"]

