"""Sharding rules: map parameter/batch/cache pytrees to PartitionSpecs.

Heuristic Megatron-style placement: for each parameter leaf, shard the
largest eligible (divisible, >= axis size) dimension over ``model``;
layer-stack dims are left unsharded. DWFL's flat [W, d] training buffer
puts its worker rows over ``data`` (and ``pod``) and, model-sharded, its
columns over ``model`` (``flat_buffer_sharding``). Small leaves (norm
scales, biases, gate vectors) replicate.

This is deliberately rule-based rather than per-tensor hand-annotation:
with 10 architecture families the rule set IS the config surface, and XLA's
SPMD propagation handles the activation side.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def _model_dim(shape, skip: int, msize: int, path: str = "") -> Optional[int]:
    """Pick the dim to shard over 'model': the LARGEST divisible dim (ties
    break toward later dims — column parallel); skip leading stack dims.
    Path overrides: expert stacks shard the expert dim (expert parallelism);
    *down/*out projections prefer the penultimate (row parallel) dim."""
    eligible = [d for d in range(skip, len(shape))
                if shape[d] >= msize and shape[d] % msize == 0]
    if not eligible:
        return None
    # mLSTM: q/k/if projections feed head-dim contractions that cannot be
    # usefully head-sharded (4 fat heads); replicating these weights lets
    # XLA gather the up-projected branch ONCE per layer instead of
    # all-reducing three projection partial-sums (§Perf xlstm iteration 2).
    if "mlstm" in path and any(t in path for t in ("w_q", "w_k", "w_if")):
        return None
    # sLSTM recurrent weights: replicated (4 fat heads don't split 16 ways;
    # a sharded R would add a per-timestep collective to the 32k-step scan).
    # NOTE (§Perf xlstm iterations 3-4): replicating the whole cell
    # (w_zifo too) was REFUTED — XLA then shards the scan carry itself and
    # inserts per-step partial-sum all-reduces; steering carry sharding
    # needs shard_map around the scan (future work).
    if "slstm" in path and "r_zifo" in path:
        return None
    if "moe/w_" in path and len(shape) - skip >= 3:
        d = len(shape) - 3  # [.., E, in, out] -> shard experts
        if d in eligible:
            return d
    if any(t in path for t in ("w_down", "wo", "w_out")) and len(shape) >= 2:
        d = len(shape) - 2
        if d in eligible:
            return d
    return max(eligible, key=lambda d: (shape[d], d))


def _path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_specs(params_shape, *, mesh):
    """PartitionSpec pytree for one model's param tree (serving): each
    leaf's model dim over ``model``, layer-stack dims unsharded."""
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    def spec_for(path, leaf):
        d = _model_dim(leaf.shape, 0, msize, _path_str(path))
        # guard: never place 'model' on what is actually a layer-stack dim —
        # only shard among the last 3 dims of the leaf.
        if d is not None and d < leaf.ndim - 3:
            d = None
        spec = [None] * leaf.ndim
        if d is not None:
            spec[d] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def cache_specs(cache_shape, *, mesh, data_axes: Tuple[str, ...] = ("data",),
                batch_size: int = 0):
    """KV/state caches: [L(,k), B, ...] stacked — shard the batch dim
    (identified by size == batch_size) over data, and a trailing feature
    dim over model where eligible."""
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    dsize = int(np.prod([_axis_size(mesh, a) for a in data_axes])) if data_axes else 0

    def spec_for(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        spec = [None] * leaf.ndim
        # caches are stacked (L[,k], B, ...); shard the first dim whose size
        # equals the batch size (avoids ever hitting a layer-stack dim).
        if data_axes and dsize and batch_size and batch_size % dsize == 0:
            for d in range(leaf.ndim - 1):
                if shape[d] == batch_size:
                    spec[d] = data_axes if len(data_axes) > 1 else data_axes[0]
                    break
        # shard a trailing feature dim over model (kv heads usually too few;
        # feature dims like P, N, d_model often eligible)
        for d in range(leaf.ndim - 1, max(leaf.ndim - 3, 0), -1):
            if spec[d] is None and shape[d] >= msize and shape[d] % msize == 0:
                spec[d] = "model"
                break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def flat_buffer_sharding(spec, mesh=None, replicate_axis: Optional[str] = None):
    """Placement rule for the persistent flat DWFL buffer of an
    exchange.FlatSpec: last (column) axis over 'model' when the spec
    carries a ShardLayout, leading replicate axis (fleet [R, W, width])
    over ``replicate_axis``. Returns the PartitionSpec, or the
    NamedSharding when ``mesh`` is given (device_put the buffer with it
    before entering the sharded round)."""
    from repro.shard.round import partition_spec
    p = partition_spec(spec, replicate_axis=replicate_axis)
    return p if mesh is None else NamedSharding(mesh, p)


def _axis_size(mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
