"""Per-(arch × shape × mesh) lowering cases for the dry-run.

``build_case`` returns the step function plus fully-sharded
ShapeDtypeStruct arguments (weak-type-correct, shardable, zero allocation)
for one of the three step kinds:

    train    — the full DWFL round on the flat [W, d] buffer (per-worker
               grads + local step + exchange)
    prefill  — forward building the KV/state cache
    decode   — ONE new token against a seq_len cache

Also computes MODEL_FLOPS (6·N·D train / 2·N_active·D decode-prefill) for
the roofline's useful-compute ratio.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import get_arch, get_shape
from repro.core import exchange as X
from repro.core.protocol import (ProtocolConfig, init_worker_params,
                                 make_flat_train_step)
from repro.launch import mesh as mesh_lib
from repro.launch import shardings as sh
from repro.models import model as M

# tp_hints / remat_policy="dots" were measured and REFUTED for the
# production mesh (§Perf qwen2-72b iterations 1-2) — defaults stay off.
DRYRUN_OVERRIDES = dict(param_dtype="bfloat16", compute_dtype="bfloat16",
                        remat=True)


@dataclass
class Case:
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    tokens: float            # tokens processed per step (global)
    model_flops: float
    n_params: int
    kind: str
    out_shardings: Any = None
    donate_argnums: Tuple[int, ...] = ()

    def jit(self):
        return jax.jit(self.fn, out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _attach(shape_tree, spec_tree, mesh):
    return jax.tree_util.tree_map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shape_tree, spec_tree)


def _count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def active_param_fraction(cfg: ModelConfig, n_params: int) -> float:
    """MoE: fraction of params active per token."""
    if not cfg.num_experts:
        return 1.0
    n_moe_layers = cfg.num_layers - cfg.first_dense_layers
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    inactive = n_moe_layers * (cfg.num_experts - cfg.num_experts_per_tok) * per_expert
    return max(0.05, (n_params - inactive) / n_params)


def _train_batch_shapes(cfg: ModelConfig, shp: ShapeConfig, W: int):
    b = max(1, shp.global_batch // W)
    S = shp.seq_len
    d = cfg.d_model
    if cfg.is_encoder_decoder:
        return {"embeds": ((W, b, cfg.encoder_seq_len, d), jnp.bfloat16),
                "tokens": ((W, b, S), jnp.int32)}
    if cfg.embedding_inputs:
        return {"embeds": ((W, b, S, d), jnp.bfloat16),
                "labels": ((W, b, S), jnp.int32)}
    return {"tokens": ((W, b, S), jnp.int32)}


def _serve_batch_shapes(cfg: ModelConfig, B: int, S: int, decode: bool):
    d = cfg.d_model
    if decode:
        return {"tokens": ((B, 1), jnp.int32)}
    if cfg.is_encoder_decoder:
        return {"embeds": ((B, cfg.encoder_seq_len, d), jnp.bfloat16),
                "tokens": ((B, S), jnp.int32)}
    if cfg.embedding_inputs:
        return {"embeds": ((B, S, d), jnp.bfloat16),
                "labels": ((B, S), jnp.int32)}
    return {"tokens": ((B, S), jnp.int32)}


def build_case(arch: str, shape: str, mesh, *, multi_pod: bool = False,
               proto: Optional[ProtocolConfig] = None,
               overrides: Optional[dict] = None) -> Case:
    cfg = get_arch(arch, shape).replace(**(overrides or DRYRUN_OVERRIDES))
    shp = get_shape(shape)
    waxes = mesh_lib.worker_axes(multi_pod)
    dataxes = waxes  # serving shards batch over the same axes
    W = mesh_lib.n_workers(mesh)

    key0 = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: M.init_params(k, cfg), key0)
    n_params = _count(params_shape)
    act_frac = active_param_fraction(cfg, n_params)

    if shp.kind == "train":
        proto = proto or ProtocolConfig(scheme="dwfl", n_workers=W,
                                        gamma=0.01, eta=0.5, clip=1.0)
        proto = dataclasses.replace(proto, n_workers=W)
        spec = X.make_flat_spec(jax.eval_shape(
            lambda k: init_worker_params(k, cfg, W), key0))
        step = make_flat_train_step(cfg, proto, spec.unravel_row)
        # the [W, d] f32 buffer, its rows sharded over the worker axes
        wp = _sds((W, spec.d), jnp.float32, mesh,
                  P(waxes if len(waxes) > 1 else waxes[0], None))
        bshapes = _train_batch_shapes(cfg, shp, W)
        batch = {k: _sds(s, dt, mesh,
                         P(waxes if len(waxes) > 1 else waxes[0],
                           *([None] * (len(s) - 1))))
                 for k, (s, dt) in bshapes.items()}
        keyspec = _sds(key0.shape, key0.dtype, mesh, P())
        tokens = float(shp.global_batch * shp.seq_len)
        out_sh = (wp.sharding, NamedSharding(mesh, P()))  # (params', metrics)
        return Case(f"{arch}|{shape}", step, (wp, batch, keyspec),
                    tokens, 6.0 * n_params * act_frac * tokens, n_params,
                    "train", out_shardings=out_sh, donate_argnums=(0,))

    params = _attach(params_shape,
                     sh.param_specs(params_shape, mesh=mesh),
                     mesh)

    msize = mesh_lib.model_size(mesh)

    def logits_spec(B_, lead_axes):
        lead = (lead_axes if len(lead_axes) > 1 else lead_axes[0]) if lead_axes else None
        vshard = "model" if cfg.vocab_size % msize == 0 else None
        return NamedSharding(mesh, P(lead, None, vshard))

    if shp.kind == "prefill":
        def step(p, b):
            return M.prefill(p, b, cfg)
        bshapes = _serve_batch_shapes(cfg, shp.global_batch, shp.seq_len, False)
        batch = {k: _sds(s, dt, mesh,
                         P(dataxes if len(dataxes) > 1 else dataxes[0],
                           *([None] * (len(s) - 1))))
                 for k, (s, dt) in bshapes.items()}
        tokens = float(shp.global_batch * shp.seq_len)
        out_shape = jax.eval_shape(step, params, batch)
        cache_out = sh.named(mesh, sh.cache_specs(
            out_shape[1], mesh=mesh, data_axes=dataxes,
            batch_size=shp.global_batch))
        out_sh = (logits_spec(shp.global_batch, dataxes), cache_out)
        return Case(f"{arch}|{shape}", step, (params, batch),
                    tokens, 2.0 * n_params * act_frac * tokens, n_params,
                    "prefill", out_shardings=out_sh)

    # decode
    B = shp.global_batch
    def step(p, b, cache, idx):
        return M.decode_step(p, b, cache, idx, cfg)
    cache_shape = jax.eval_shape(lambda: M.init_cache(cfg, B, shp.seq_len))
    dax = dataxes if B >= W else ()
    cache = _attach(cache_shape,
                    sh.cache_specs(cache_shape, mesh=mesh, data_axes=dax,
                                   batch_size=B),
                    mesh)
    bshapes = _serve_batch_shapes(cfg, B, shp.seq_len, True)
    batch = {k: _sds(s, dt, mesh,
                     P((dax if len(dax) > 1 else dax[0]) if dax else None,
                       *([None] * (len(s) - 1))))
             for k, (s, dt) in bshapes.items()}
    idx = _sds((), jnp.int32, mesh, P())
    tokens = float(B)
    cache_out_sh = jax.tree_util.tree_map(lambda s: s.sharding, cache)
    out_sh = (logits_spec(B, dax), cache_out_sh)
    return Case(f"{arch}|{shape}", step, (params, batch, cache, idx),
                tokens, 2.0 * n_params * act_frac * tokens, n_params,
                "decode", out_shardings=out_sh, donate_argnums=(2,))
