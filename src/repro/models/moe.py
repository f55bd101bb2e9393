"""Mixture-of-Experts FFN: softmax router over every routed expert, greedy
top-k, no token dropped, grouped products over the experts this layer holds.

The router keeps its full width (``num_experts`` outputs) and each token
its ``num_experts_per_tok`` choices; the top-k gates are renormalised only
where ``norm_topk_prob`` says so, then scaled by ``routed_scaling_factor``.
A layer holds experts [first_expert, first_expert + experts_local) — all
of them unless the config is cut to one chip's share of an
expert-parallel deployment (model-configs guide §4). The (token, slot)
pairs routed to a held expert are sorted by expert and go through one
grouped product per weight (``jax.lax.ragged_dot``): every pair is
computed, none dropped, and no product is taken for an expert or a pair
this layer does not hold. The TPU's grouped product takes no batch
dimension, so under ``jax.vmap`` (one model per worker) the grouped
products run one batch element after another and the rest of the layer
stays batched. The layer's output is its held experts' part of
the routed result plus the shared experts, which every share computes
alike; the parts of all shares add up to the uncut layer
(tests/test_deepseek_v2.py).

The balance loss is DeepSeek-V2's sequence-wise one (arXiv:2405.04434
§2.2.3): per sequence, sum over experts of f_i P_i with f_i the share of
the sequence's top-k choices that go to expert i times E, and P_i the
mean router probability of expert i; averaged over the sequences.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def moe_init(key, cfg: ModelConfig, dtype):
    """Router [d, E] over all experts; weight stacks [held, ...] of the
    held experts, expert e's drawn from ``fold_in(key, e)`` (global index),
    so a cut holds exactly the uncut layer's experts."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    E, d, ff = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    held = jnp.arange(cfg.first_expert, cfg.first_expert + cfg.experts_local)

    def stack(k, shape, fan_in):
        return jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(k, e), shape) * (1.0 / math.sqrt(fan_in)))(
            held).astype(dtype)
    p = {
        "router": L.dense_init(kr, d, E, jnp.float32),
        "w_gate": stack(kg, (d, ff), d),
        "w_up": stack(ku, (d, ff), d),
        "w_down": stack(kd, (ff, d), ff),
    }
    if cfg.num_shared_experts:
        shared_ff = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = L.mlp_init(ks, cfg, dtype, d_ff=shared_ff)
    return p


def route(router_logits, cfg: ModelConfig):
    """[B, S, E] logits -> (gates [B, S, k], experts [B, S, k], balance
    loss): softmax over every expert, greedy top-k, gates renormalised only
    if ``norm_topk_prob``."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * cfg.routed_scaling_factor
    S = probs.shape[1]
    f = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2)) * (
        E / (S * k))                                            # [B, E]
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    return gates, idx, aux


def _one_after_another(f):
    """``f``, which ``jax.vmap`` runs once per batch element (``lax.map``)
    in place of batching it; arguments not batched are passed whole."""
    g = jax.custom_batching.custom_vmap(f)

    @g.def_vmap
    def rule(axis_size, in_batched, *args):
        def one(xs):
            it = iter(xs)
            return g(*[next(it) if b else a
                       for a, b in zip(args, in_batched)])
        out = jax.lax.map(one, tuple(a for a, b in zip(args, in_batched)
                                     if b))
        return out, jax.tree_util.tree_map(lambda _: True, out)
    return g


def _ragged_vjp(a, w, sizes, ct):
    _, back = jax.vjp(lambda a, w: jax.lax.ragged_dot(a, w, sizes), a, w)
    return back(ct)


_ragged_fwd = _one_after_another(
    lambda a, w, sizes: jax.lax.ragged_dot(a, w, sizes))
_ragged_bwd = _one_after_another(_ragged_vjp)


@jax.custom_vjp
def grouped_dot(a, w, sizes):
    """``jax.lax.ragged_dot(a, w, sizes)`` whose forward and backward
    products run one batch element after another under ``jax.vmap``
    (``custom_vmap`` has no transpose, so the backward is given here)."""
    return _ragged_fwd(a, w, sizes)


def _grouped_dot_fwd(a, w, sizes):
    return _ragged_fwd(a, w, sizes), (a, w, sizes)


def _grouped_dot_bwd(res, ct):
    a, w, sizes = res
    da, dw = _ragged_bwd(a, w, sizes, ct)
    return da, dw, None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def routed(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed output, and the balance loss.

    x: [B, S, d] -> ([B, S, d], scalar)."""
    B, S, d = x.shape
    T, k, held = B * S, cfg.num_experts_per_tok, cfg.experts_local
    logits = x.astype(jnp.float32) @ params["router"]
    gates, idx, aux = route(logits, cfg)
    local = idx.reshape(T * k) - cfg.first_expert
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)        # not held: one group past
    order = jnp.argsort(group, stable=True)
    # a token picks an expert once, so at most T * min(k, held) pairs are
    # held here; they sort first, the rest never enter a product
    order = order[:T * min(k, held)]
    sizes = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32),
                    axis=0)[:held]
    token = order // k
    xs = x.reshape(T, d)[token]
    # the TPU's grouped product leaves the rows past the last group (the
    # pairs held elsewhere) unwritten, in its output and in the gradient
    # of its input: those rows are zeroed on the way in and out, both ways
    held_row = (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]

    def grouped(a, w):
        a = jnp.where(held_row, a, 0.0)
        return jnp.where(held_row, grouped_dot(a, w, sizes), 0.0)
    with jax.named_scope("dwfl.grad.moe.experts"):
        h = jax.nn.silu(grouped(xs, params["w_gate"]))
        h = h * grouped(xs, params["w_up"])
        ys = grouped(h, params["w_down"])                     # [M, d]
    g = jnp.where(mine, gates.reshape(T * k), 0.0)[order]
    y = jax.ops.segment_sum(ys * g[:, None].astype(ys.dtype), token,
                            num_segments=T)
    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (held experts' routed part + shared experts [B, S, d],
    balance loss scalar)."""
    with jax.named_scope("dwfl.grad.moe"):
        y, aux = routed(params, x, cfg)
        if cfg.num_shared_experts:
            y = y + L.mlp_apply(params["shared"], x, cfg)
        return y, aux
