"""Worker-axis sharded execution of the sparse-mixing DWFL round.

``repro.shard.round`` splits the flat buffer's COLUMNS (model axis) —
every device still carries all N worker rows, so N itself is capped by
one device's memory and compute. This module splits the WORKER axis
instead: with S shards and N % S == 0, shard s owns worker rows
[s·Nb, (s+1)·Nb) of the persistent [N, d] buffer (Nb = N/S) and

* the per-worker gradient pass — the round's dominant cost at scale —
  runs only on the local row block's Nb workers against the local batch
  slab: perfect compute/memory scaling of the SGD half;
* DP + AWGN noise is drawn locally from the counter-hash generator with
  the block's GLOBAL row offset (``row0`` in dp_mix._normal_pair_hash),
  so the union of the per-shard noise streams IS the single-device
  stream (bitwise); the round's RESULTS are ULP-close to the unsharded
  sparse round rather than bitwise — the elementwise mix chain fuses
  (FMA-contracts) differently around the collective boundary, the same
  association caveat the sparse path already carries vs the dense GEMM
  (tests/test_sparse.py runs the 2-device subprocess check);
* mixing gathers neighbor rows from ONE tiled ``all_gather`` of the
  noised buffer z = x + n/c — the [N, Dp] transient is the only
  full-population tensor in the program (a neighbor can live on any
  shard; with the paper-scale d this transient is what the network
  itself would carry over the air, and it is freed within the round).

Only the sparse neighbor-list path is supported: worker-scale N is
exactly the regime where a dense [N, N] W (let alone the dense mixing
contraction) must not exist, so the step requires the per-round W to be
a repro.net.sparse.SparseW (``ProtocolConfig(sparse_neighbors=k)``).

The mesh carries a ``workers`` axis (launch.mesh.make_worker_mesh) and
may extend to the full 3-D ("replicas", "workers", "model") shape —
axes other than ``workers`` are untouched here (inputs replicated over
them), composing with the fleet vmap outside exactly like the 1-D
paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import exchange as exchange_lib
from repro.core import protocol as protocol_lib
from repro.core.exchange import FlatSpec
from repro.kernels.dp_mix import dp_mix as K
from repro.kernels.dp_mix import ops as mix_ops


def worker_partition_spec(lead_axes: int = 1):
    """PartitionSpec of the [.., N, d] flat buffer row-sharded over the
    ``workers`` mesh axis (columns replicated)."""
    from jax.sharding import PartitionSpec as P
    parts = [None] * (lead_axes + 1)
    parts[-2] = "workers"
    return P(*parts)


def _row_slice(v, row0, nb):
    """Rows [row0, row0+nb) of a replicated per-worker [N, ...] array
    (row0 traced — lax.axis_index-derived; N % S == 0, so the slice never
    clamps)."""
    return jax.lax.dynamic_slice_in_dim(v, row0, nb, axis=0)


def worker_window_round(p_loc, g_loc, seed, plan, row0, n_workers, *,
                        gamma: float, eta: float, axis: str):
    """One worker shard's row window of the fused sparse round.

    ``p_loc``/``g_loc`` are the local [Nb, d] row block; ``plan`` the
    full-population MixPlan (replicated — its per-receiver vectors are
    [N], cheap) whose ``W`` is a SparseW; ``row0`` the block's global
    first row. Mirrors ops.dp_mix_round_sparse's padding geometry and
    dp_mix._sparse_round_math's arithmetic exactly — noise counters are
    (row0 + local_row)·Dp + col, so every real row computes the bitwise
    arithmetic of the unsharded round; only neighbor values arrive via
    the all_gather instead of a local row index (results ULP-close, not
    bitwise — module docstring)."""
    from repro.net.sparse import SparseW
    sw = plan.W
    if not isinstance(sw, SparseW):
        raise TypeError("worker-axis sharding requires a sparse neighbor "
                        "list (ProtocolConfig(sparse_neighbors=k)); got a "
                        f"dense {type(sw).__name__} mixing matrix")
    nb, d = p_loc.shape
    Dp = -(-d // K.LANES) * K.LANES
    p = jnp.pad(p_loc.astype(jnp.float32), ((0, 0), (0, Dp - d)))
    g = jnp.pad(g_loc.astype(jnp.float32), ((0, 0), (0, Dp - d)))
    x = p - gamma * g

    col = lambda v: v.reshape(nb, 1)
    rowv = lambda v: col(_row_slice(jnp.asarray(v, jnp.float32), row0, nb))
    c = jnp.asarray(plan.c, jnp.float32).reshape(())
    amp = rowv(plan.amp)
    selfs = (jnp.float32(1.0) if plan.self_scale is None
             else rowv(plan.self_scale))
    if plan.m_scale is None:
        mscale = 1.0 / (c * max(n_workers - 1, 1))
    else:
        mscale = rowv(plan.m_scale)
    listen = jnp.float32(1.0) if plan.listen is None else rowv(plan.listen)
    idx_loc = _row_slice(jnp.asarray(sw.idx, jnp.int32), row0, nb)
    w_loc = _row_slice(jnp.asarray(sw.w, jnp.float32), row0, nb)
    self_w = rowv(sw.self_w)

    if plan.noisy:
        g_n, g_m = K._normal_pair_hash(
            (nb, Dp), Dp, 0, jnp.asarray(seed, jnp.int32).reshape(-1)[0],
            row0=row0)
        nf = (amp / c) * g_n
        z = x + nf
    else:
        z = x
    # the one full-population tensor: every shard's noised block, tiled
    # back to global row order — neighbor gathers then stay local
    z_full = jax.lax.all_gather(z, axis, axis=0, tiled=True)
    acc = self_w * z
    for s in range(idx_loc.shape[1]):
        acc = acc + w_loc[:, s:s + 1] * z_full[idx_loc[:, s]]
    if plan.noisy:
        sigma_m = jnp.asarray(plan.sigma_m, jnp.float32).reshape(())
        upd_px = acc + (mscale * sigma_m) * g_m - selfs * nf
    else:
        upd_px = acc
    out = x + eta * listen * (upd_px - x)
    return out[:, :d].astype(p_loc.dtype)


def _check_worker_mesh(proto, mesh, axis: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    S = sizes[axis]
    if proto.n_workers % S != 0:
        raise ValueError(f"n_workers={proto.n_workers} must divide evenly "
                         f"over the {S} {axis!r} shards")
    return S


def make_worker_sharded_dynamic_flat_train_step(cfg, proto, spec: FlatSpec,
                                                mesh, axis: str = "workers",
                                                remat: bool = False):
    """Worker-axis sharded twin of protocol.make_dynamic_flat_train_step:

        step(flat, batch, key, chan, W) -> (flat', metrics)

    ``flat`` is the [N, d] buffer row-sharded over the mesh's ``axis``
    (device_put with worker_partition_spec() first); ``batch`` leaves are
    worker-leading and sharded the same way; ``key``/``chan``/``W`` are
    replicated (``W`` MUST be a repro.net.sparse.SparseW — resolve_spec
    routes here only for ProtocolConfig(sparse_neighbors>0)). The key
    split, noise counters, per-row gradients and the gathered [N]-vector
    metrics (loss/grad_norm) match the unsharded sparse step bitwise on
    CPU; the mixed buffer itself is ULP-close (module docstring) and
    param_norm is a psum of per-shard partials — ULP-level, like the
    model-axis mesh mode."""
    if spec.layout is not None:
        raise ValueError("worker-axis sharding takes the unsharded exact-d "
                         "FlatSpec (model-axis column windows don't compose "
                         "with the row split yet)")
    S = _check_worker_mesh(proto, mesh, axis)
    if proto.n_workers < 2:
        raise ValueError("worker-axis sharding needs n_workers >= 2")
    Nb = proto.n_workers // S
    local_grads = protocol_lib._make_flat_local_pass(cfg, proto,
                                                     spec.unravel_row,
                                                     remat=remat)
    xspec = protocol_lib._mixing_spec(proto, dynamic=True)
    gamma, eta = proto.gamma, proto.eta
    n_workers = proto.n_workers

    def run(flat_loc, batch_loc, key, chan, W):
        k_n, k_x = jax.random.split(key)
        losses_b, g_loc, gnorms_b = local_grads(flat_loc, batch_loc)
        plan = xspec.plan(proto, chan, k_x, W_arg=W)
        seed = mix_ops.seed_from_key(k_n)
        row0 = jax.lax.axis_index(axis).astype(jnp.int32) * Nb
        flat_loc = worker_window_round(flat_loc, g_loc, seed, plan, row0,
                                       n_workers, gamma=gamma, eta=eta,
                                       axis=axis)
        losses = jax.lax.all_gather(losses_b, axis, axis=0, tiled=True)
        gnorms = jax.lax.all_gather(gnorms_b, axis, axis=0, tiled=True)
        sq = jax.lax.psum(jnp.sum(flat_loc.astype(jnp.float32) ** 2), axis)
        metrics = {"loss": jnp.mean(losses), "grad_norm": jnp.mean(gnorms),
                   "param_norm": jnp.sqrt(sq)}
        return flat_loc, metrics

    from jax.sharding import PartitionSpec as P
    return jax.shard_map(run, mesh=mesh,
                         in_specs=(P(axis, None), P(axis), P(), P(), P()),
                         out_specs=(P(axis, None), P()), check_vma=False)
