"""MoE decoder transformer (qwen3-moe, deepseek-moe).

Identical attention trunk to the dense transformer; the FFN is a routed MoE
(repro.models.moe), with optional shared experts and optional leading dense
layers (deepseek-moe: first layer dense). Attention is the config's kind
(latent attention when ``kv_lora_rank`` > 0). The balance loss is summed
over the MoE layers through the layer scan and returned next to the
logits. ``cfg.remat`` checkpoints each block of both layer scans in
training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T


def moe_block_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.norm_init(cfg, dtype),
        "attn": L.attention_init(k1, cfg, dtype),
        "norm2": L.norm_init(cfg, dtype),
        "moe": M.moe_init(k2, cfg, dtype),
    }


def moe_block_apply(params, x, cfg: ModelConfig, positions, mode,
                    cache=None, cache_index=None):
    h, new_cache = L.attention_apply(
        params["attn"], L.norm_apply(params["norm1"], x, cfg), cfg, positions,
        mode=mode, cache=cache, cache_index=cache_index)
    x = x + h
    y, aux = M.moe_apply(params["moe"], L.norm_apply(params["norm2"], x, cfg), cfg)
    return x + y, new_cache, aux


def init(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kd, km, kf = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers
    n_moe = cfg.num_layers - n_dense
    p = {
        "embed": L.embed_init(ke, cfg, dtype),
        "moe_blocks": L.stacked(jax.random.split(km, n_moe),
                                lambda k: moe_block_init(k, cfg, dtype)),
        "final_norm": L.norm_init(cfg, dtype),
    }
    if n_dense:
        p["dense_blocks"] = L.stacked(jax.random.split(kd, n_dense),
                                      lambda k: T.block_init(k, cfg, dtype))
    return p


def forward(params, batch, cfg: ModelConfig, *, mode="train",
            cache=None, cache_index=None, use_pallas: bool = False):
    """Returns (logits, new_cache, aux_loss)."""
    x = T._embed_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    positions = T._positions_for(batch, cfg, S, B,
                                 offset=cache_index if mode == "decode" else 0)

    n_dense = cfg.first_dense_layers
    new_cache = {"dense": None, "moe": None}

    # --- leading dense blocks ---------------------------------------------
    remat = cfg.remat and mode == "train"
    if n_dense:
        if mode == "decode":
            def dense_scan(h, bc):
                blk, c = bc
                h, c2 = T.block_apply(blk, h, cfg, positions, "decode",
                                      cache=c, cache_index=cache_index)
                return h, c2
            x, new_cache["dense"] = jax.lax.scan(
                dense_scan, x, (params["dense_blocks"], cache["dense"]))
        else:
            def dense_blk(h, blk):
                return T.block_apply(blk, h, cfg, positions, mode)
            if remat:
                dense_blk = jax.checkpoint(dense_blk)

            def dense_scan(h, blk):
                return dense_blk(h, blk)
            x, dc = jax.lax.scan(dense_scan, x, params["dense_blocks"])
            new_cache["dense"] = dc if mode == "prefill" else None

    # --- MoE blocks ----------------------------------------------------------
    def moe_body(carry, blk, c=None):
        h, aux = carry
        h, c2, a = moe_block_apply(blk, h, cfg, positions, mode,
                                   cache=c, cache_index=cache_index)
        return (h, aux + a), c2

    if remat:
        def _blk(h, blk):
            h2, _, a = moe_block_apply(blk, h, cfg, positions, "train")
            return h2, a
        body = jax.checkpoint(_blk)
    if mode == "decode":
        def moe_scan(carry, bc):
            blk, c = bc
            return moe_body(carry, blk, c)
        (x, aux), new_cache["moe"] = jax.lax.scan(
            moe_scan, (x, jnp.float32(0.0)), (params["moe_blocks"], cache["moe"]))
    else:
        if remat:
            def moe_scan(carry, blk):
                h, aux = carry
                h2, a = body(h, blk)
                return (h2, aux + a), None
        else:
            def moe_scan(carry, blk):
                return moe_body(carry, blk)
        (x, aux), mc = jax.lax.scan(moe_scan, (x, jnp.float32(0.0)), params["moe_blocks"])
        new_cache["moe"] = mc if mode == "prefill" else None

    x = L.norm_apply(params["final_norm"], x, cfg)
    logits = L.unembed_apply(params["embed"], x, cfg)
    return logits, (new_cache if mode != "train" else None), aux
