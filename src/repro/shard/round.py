"""Model-axis sharded execution of the fused flat-buffer DWFL round.

The persistent [W, d] buffer (exchange.FlatSpec) is split column-wise over
a ``model`` mesh axis (ShardLayout); each shard runs the WHOLE fused
dp_mix pipeline — local SGD, on-chip DP noise, the [N, N]×[N, d_shard]
mixing matmul, self-correction, AWGN — on its own column window, with the
noise counters offset to the window's global columns so the union of the
per-shard CPU streams IS the single-device stream (bitwise; DESIGN.md
§11). Only the per-worker gradient pass needs full rows, and the mesh
step obtains them GATHER-FREE by splitting the WORKER axis instead of
replicating the model: with S shards and Wp = S·ceil(W/S) (worker rows
zero-padded to divisibility), shard s owns worker block
[s·Wb, (s+1)·Wb) and

* a chunk-scheduled ``all_to_all`` (spec.chunk_plan — leaf x window
  chunks capped at ``max_chunk_cols``) trades its column window for its
  worker block's full rows, one chunk segment at a time (just-in-time
  gather, discarded after the transpose);
* the clipped gradients run on the local [Wb, d] row block — the exact
  unsharded subprogram (protocol._make_flat_local_pass) on W/S workers,
  optionally with the model's layer-level checkpoint (``remat=True``);
* the reverse ``all_to_all`` scatters each chunk's gradient columns
  straight into the owning shard's window (the reduce in reduce-scatter
  is a no-op here: worker-split grads are disjoint, never summed), and
  the O(d) dp_mix round stays fully local as before.

Memory contract: the persistent buffer is d/S per device AND the round's
peak is ~(W·d)/S per device — the [Wb, d] row block, its gradient, plus
transients bounded by the chunk budget (~W·max_chunk_cols elements per
collective) — and the activations of the grad pass on top (one input per
layer and the one layer being differentiated under ``remat``).
No full [W, d] materialization exists anywhere in the sharded program
(statically enforced: repro.analysis's ``gather`` checker ERRORs on any
full-width all_gather of the buffer). Compute also drops to W/S
grad-pass workers per device — on a single-socket host the sharded round
therefore WINS throughput instead of paying an S-fold redundant gather
(BENCH_shard.json).

Device scopes: the ``all_to_all`` pair (and the worker-axis pad) under
``dwfl.shard``, the gradient pass under ``dwfl.grad`` (protocol), the
mix under ``dwfl.mix``, the metrics and their [W] gathers under
``dwfl.telemetry``.

Two execution modes share one window primitive (``shard_window_round``):

* ``mesh=None`` — LOGICAL sharding: the padded buffer lives on one device
  and the S windows run as a vmap. No collectives, no multi-device
  runtime; used for tests, for checkpoint re-layout verification, and as
  the fallback when fewer devices than shards exist.
* ``mesh`` with a ``model`` axis — shard_map: each device holds
  [W, shard_width] of the buffer, col0 = axis_index("model")·shard_width.
  Composable with the fleet's replicate axis into a 2-D
  ("replicas", "model") mesh (``make_fleet_sharded_step``).

Both modes reproduce the unsharded round bitwise on the real columns
(CPU), because every column's arithmetic is independent and the noise
stream is counter-addressed (tests/test_shard.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import protocol as protocol_lib
from repro.core.exchange import FlatSpec
from repro.kernels.dp_mix import ops as mix_ops
from repro.shard.layout import ShardLayout


def partition_spec(spec: FlatSpec, replicate_axis: Optional[str] = None):
    """jax PartitionSpec for the physical flat buffer of ``spec``: last
    axis over 'model' when sharded, leading replicate axis (fleet) over
    ``replicate_axis``."""
    from jax.sharding import PartitionSpec as P
    parts = [None] * (spec.lead_axes + 1)
    if replicate_axis is not None:
        parts[0] = replicate_axis
    if spec.n_shards > 1:
        parts[-1] = "model"
    return P(*parts)


def shard_window_round(p_loc, g_loc, seed, plan, col0, layout: ShardLayout,
                       *, gamma: float, eta: float, impl=None):
    """One shard's column window of the fused round: dp_mix on the local
    [W, shard_width] slice with globally-addressed noise counters, padding
    columns (global col ≥ layout.d) pinned back to exactly zero — the
    sharded-buffer invariant that keeps re-layouts a pure pad/slice."""
    out = mix_ops.dp_mix_round_plan(
        p_loc, g_loc, seed, plan, gamma=gamma, eta=eta, impl=impl,
        col0=col0, counter_width=layout.counter_width)
    gcol = jnp.asarray(col0, jnp.int32) + jnp.arange(p_loc.shape[-1],
                                                     dtype=jnp.int32)
    return jnp.where(gcol[None, :] < layout.d, out, 0.0).astype(out.dtype)


def dp_mix_round_sharded(flat, g, seed, plan, layout: ShardLayout, *,
                         gamma: float, eta: float, impl=None):
    """Logical (single-device) sharded round: the S column windows of the
    padded [W, padded_width] buffer run as one vmap. Bitwise-equal on the
    real columns to ops.dp_mix_round on the unpadded [W, d] buffer."""
    S, ds = layout.n_shards, layout.shard_width
    Wn = flat.shape[0]
    ps = flat.reshape(Wn, S, ds)
    gs = g.reshape(Wn, S, ds)
    col0s = jnp.asarray(layout.col_offsets())
    out = jax.vmap(
        lambda p, gg, c0: shard_window_round(
            p, gg, seed, plan, c0, layout, gamma=gamma, eta=eta, impl=impl),
        in_axes=(1, 1, 0), out_axes=1)(ps, gs, col0s)
    return out.reshape(Wn, S * ds)


def _padded_local_grads(cfg, proto, spec: FlatSpec, *, remat: bool = False):
    """The flat-buffer gradient pass on a PADDED buffer: run the exact
    unsharded subprogram on the canonical [:, :d] view, re-pad the
    gradients with exact zeros (padding columns carry no parameters, so
    their gradient IS zero). Row count is free — the mesh path calls this
    on its [Wb, width] worker block, the logical path on all W rows —
    because the base pass vmaps over whatever leading axis it gets.
    ``remat`` turns on the model's layer-level checkpoint (``cfg.remat``):
    each layer's activations are recomputed in the backward pass, so only
    the layer inputs are saved — activation memory for the price of a
    second forward, for configs whose activations dominate the row
    block."""
    base = protocol_lib._make_flat_local_pass(cfg, proto, spec.unravel_row,
                                              remat=remat)
    d, width = spec.d, spec.width

    def local_grads(flat_full, batch):
        losses, g, gnorms = base(flat_full[:, :d], batch)
        if width > d:
            g = jnp.pad(g, ((0, 0), (0, width - d)))
        return losses, g, gnorms

    return local_grads


def _gather_block_rows(flat_p, axis: str, layout: ShardLayout, segs):
    """Worker-split gather: trade this shard's [Wp, shard_width] column
    slab for its worker BLOCK's full rows [Wb, padded_width], one chunk
    segment per ``all_to_all`` (tiled: split the padded worker axis into
    the S blocks, concatenate the S windows' spans along columns). Each
    collective moves one segment — the transient is [Wb, S·seg] elements,
    bounded by the chunk budget — and the segment transposes are
    reassembled window-major into canonical column order."""
    S, sw = layout.n_shards, layout.shard_width
    pieces = [
        (b - a,
         jax.lax.all_to_all(flat_p[:, a:b], axis, split_axis=0,
                            concat_axis=1, tiled=True))   # [Wb, S*(b-a)]
        for a, b in segs
    ]
    cols = [seg[:, s * w:(s + 1) * w]
            for s in range(S) for w, seg in pieces]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _scatter_grad_cols(g_rows, axis: str, layout: ShardLayout, segs):
    """The reverse chunk schedule: [Wb, padded_width] row-block gradients
    -> every worker's [Wp, shard_width] gradient columns on the OWNING
    shard. Worker-split gradients are disjoint across devices, so the
    reduce of a reduce-scatter is a no-op and the scatter is the inverse
    ``all_to_all`` (columns split per window, worker blocks concatenated
    back in order) — pure data movement, bitwise whatever the segment
    partition."""
    S, sw = layout.n_shards, layout.shard_width
    outs = []
    for a, b in segs:
        parts = jnp.concatenate(
            [g_rows[:, s * sw + a:s * sw + b] for s in range(S)], axis=1)
        outs.append(jax.lax.all_to_all(parts, axis, split_axis=1,
                                       concat_axis=0, tiled=True))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _check_mesh(spec: FlatSpec, mesh, axis: str):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    if sizes[axis] != spec.layout.n_shards:
        raise ValueError(f"layout has {spec.layout.n_shards} shards but "
                         f"mesh {axis!r} axis has {sizes[axis]} devices")


def _local_round_factory(cfg, proto, spec: FlatSpec, *, dynamic: bool,
                         axis: Optional[str], impl=None,
                         remat: bool = False):
    """Build the per-network round over the LOCAL shard slab.

    axis=None: the logical mode — the function takes the whole padded
    buffer and runs dp_mix_round_sharded. axis="model": the shard_map
    body — the function takes [W, shard_width], runs the gather-free
    worker-split grad pass (module docstring), and mixes its own
    window."""
    if spec.layout is None:
        raise ValueError("sharded round requires a FlatSpec with a "
                         "ShardLayout (exchange.make_flat_spec(..., "
                         "n_shards=S))")
    layout = spec.layout
    chan = None if dynamic else proto.channel()
    xspec = protocol_lib._flat_spec(proto, dynamic=dynamic)
    local_grads = _padded_local_grads(cfg, proto, spec, remat=remat)
    gamma, eta = proto.gamma, proto.eta

    def run(flat, batch, key, chan_t=None, W_t=None):
        if dynamic:
            k_n, k_x = jax.random.split(key)
            ch = chan_t
        else:
            k_n, k_m, k_x = jax.random.split(key, 3)
            ch = chan
        if axis is None:
            col0 = None
            losses, g_own, gnorms = local_grads(flat, batch)
        else:
            # Gather-free worker-split grad pass: trade this shard's
            # column slab for its worker block's full rows (one chunk
            # segment per collective), run the exact unsharded subprogram
            # on W/S workers, scatter the gradient columns back to their
            # owning windows. No [W, padded_width] replica ever exists.
            S, sw = layout.n_shards, layout.shard_width
            Wn = proto.n_workers
            Wb = -(-Wn // S)
            Wp = Wb * S
            idx = jax.lax.axis_index(axis)
            col0 = idx.astype(jnp.int32) * sw
            segs = spec.chunk_plan.exec_segments()
            with jax.named_scope("dwfl.shard"):
                fl_p = flat if Wp == Wn else jnp.pad(
                    flat, ((0, Wp - Wn), (0, 0)))
                rows = _gather_block_rows(fl_p, axis, layout, segs)

            def _block(a):
                # zero-pad the worker axis BEFORE slicing: a clamped
                # dynamic_slice on the last device would misalign the
                # real rows against the padded flat blocks.
                if Wp > Wn:
                    a = jnp.pad(a,
                                [(0, Wp - Wn)] + [(0, 0)] * (a.ndim - 1))
                return jax.lax.dynamic_slice_in_dim(a, idx * Wb, Wb,
                                                    axis=0)

            losses_b, g_rows, gnorms_b = local_grads(
                rows, jax.tree_util.tree_map(_block, batch))
            with jax.named_scope("dwfl.shard"):
                g_own = _scatter_grad_cols(g_rows, axis, layout, segs)[:Wn]
            with jax.named_scope("dwfl.telemetry"):
                losses = jax.lax.all_gather(losses_b, axis, axis=0,
                                            tiled=True)[:Wn]
                gnorms = jax.lax.all_gather(gnorms_b, axis, axis=0,
                                            tiled=True)[:Wn]
        if proto.n_workers < 2:
            # degenerate federation: plain local SGD on the local slab
            flat = flat - gamma * g_own
            return flat, _metrics(losses, gnorms, flat)
        with jax.named_scope("dwfl.mix"):
            plan = xspec.plan(proto, ch, k_x, W_arg=W_t)
            seed = mix_ops.seed_from_key(k_n)
            if axis is None:
                flat = dp_mix_round_sharded(flat, g_own, seed, plan, layout,
                                            gamma=gamma, eta=eta, impl=impl)
            else:
                flat = shard_window_round(flat, g_own, seed, plan, col0,
                                          layout, gamma=gamma, eta=eta,
                                          impl=impl)
        return flat, _metrics(losses, gnorms, flat)

    @jax.named_scope("dwfl.telemetry")
    def _metrics(losses, gnorms, flat):
        # padding columns are exact zeros; in logical mode reduce over the
        # canonical [:, :d] view so param_norm matches the unsharded step
        # BITWISE (same reduction shape). Mesh-mode metrics are ULP-level
        # only: the gathered per-row losses/gnorms are bitwise, but XLA
        # picks the mean's reduction strategy per program, and the psum of
        # per-device partial sums associates differently.
        if axis is None:
            sq = jnp.sum(flat[:, :layout.d].astype(jnp.float32) ** 2)
        else:
            sq = jax.lax.psum(jnp.sum(flat.astype(jnp.float32) ** 2), axis)
        return {"loss": jnp.mean(losses), "grad_norm": jnp.mean(gnorms),
                "param_norm": jnp.sqrt(sq)}

    return run


def make_sharded_flat_train_step(cfg, proto, spec: FlatSpec, mesh=None,
                                 axis: str = "model", impl=None,
                                 remat: bool = False):
    """Sharded twin of protocol.make_flat_train_step (STATIC channel):

        step(flat, batch, key) -> (flat', metrics)

    ``flat`` is the physical [W, spec.width] buffer — model-axis sharded
    over ``mesh`` when given (device_put it with
    launch.shardings.flat_buffer_sharding first), logically sharded on one
    device otherwise. Bitwise-equal to the unsharded step on the canonical
    [:, :d] view (CPU)."""
    if mesh is None:
        run = _local_round_factory(cfg, proto, spec, dynamic=False,
                                   axis=None, impl=impl, remat=remat)
        return lambda flat, batch, key: run(flat, batch, key)
    _check_mesh(spec, mesh, axis)
    from jax.sharding import PartitionSpec as P
    run = _local_round_factory(cfg, proto, spec, dynamic=False, axis=axis,
                               impl=impl, remat=remat)
    return jax.shard_map(lambda flat, batch, key: run(flat, batch, key),
                         mesh=mesh, in_specs=(P(None, axis), P(), P()),
                         out_specs=(P(None, axis), P()), check_vma=False)


def make_sharded_dynamic_flat_train_step(cfg, proto, spec: FlatSpec,
                                         mesh=None, axis: str = "model",
                                         impl=None, remat: bool = False):
    """Sharded twin of protocol.make_dynamic_flat_train_step (repro.net):

        step(flat, batch, key, chan, W) -> (flat', metrics)

    ``chan``/``W`` are the per-round traced channel and mixing matrix
    (NetworkSimulator.round), replicated across the model shards — every
    shard builds the identical MixPlan and mixes its own columns."""
    if mesh is None:
        run = _local_round_factory(cfg, proto, spec, dynamic=True,
                                   axis=None, impl=impl, remat=remat)
        return lambda flat, batch, key, chan, W: run(flat, batch, key,
                                                     chan, W)
    _check_mesh(spec, mesh, axis)
    from jax.sharding import PartitionSpec as P
    run = _local_round_factory(cfg, proto, spec, dynamic=True, axis=axis,
                               impl=impl, remat=remat)
    return jax.shard_map(
        lambda flat, batch, key, chan, W: run(flat, batch, key, chan, W),
        mesh=mesh, in_specs=(P(None, axis), P(), P(), P(), P()),
        out_specs=(P(None, axis), P()), check_vma=False)


def make_fleet_sharded_step(cfg, proto, spec: FlatSpec, mesh,
                            replicate_axis: str = "replicas",
                            axis: str = "model", impl=None,
                            remat: bool = False):
    """The 2-D mesh fleet round: replicates sharded over
    ``replicate_axis``, the flat buffer's columns over ``axis``.

        step(flat, batch, keys, chans, Ws) -> (flat', metrics)

    ``flat`` is [R, W, spec.width] with sharding
    P(replicate_axis, None, axis); batch/keys/chans/Ws carry their leading
    replicate axis over ``replicate_axis`` exactly like the 1-D fleet
    path. Replicates never communicate; the only model-axis collectives
    are each replicate's chunk-segment ``all_to_all`` pair (and the [W]
    metric all_gathers) of the worker-split grad pass."""
    if spec.lead_axes != 2:
        raise ValueError("fleet sharding requires a lead_axes=2 FlatSpec "
                         "([R, W, d] buffer)")
    _check_mesh(spec, mesh, axis)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if replicate_axis not in sizes:
        raise ValueError(f"mesh has no {replicate_axis!r} axis: "
                         f"{mesh.axis_names}")
    from jax.sharding import PartitionSpec as P
    run = _local_round_factory(cfg, proto, spec, dynamic=True, axis=axis,
                               impl=impl, remat=remat)

    def body(flat, batch, keys, chans, Ws):   # local [R_loc, ...] slabs
        return jax.vmap(run)(flat, batch, keys, chans, Ws)

    rspec = P(replicate_axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(replicate_axis, None, axis), rspec, rspec, rspec,
                  rspec),
        out_specs=(P(replicate_axis, None, axis), rspec), check_vma=False)
