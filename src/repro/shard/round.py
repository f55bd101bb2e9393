"""Model-axis sharded execution of the fused flat-buffer DWFL round.

The persistent [W, d] buffer (exchange.FlatSpec) is split column-wise over
a ``model`` mesh axis (ShardLayout); each shard runs the WHOLE fused
dp_mix pipeline — local SGD, on-chip DP noise, the [N, N]×[N, d_shard]
mixing matmul, self-correction, AWGN — on its own column window, with the
noise counters offset to the window's global columns so the union of the
per-shard CPU streams IS the single-device stream (bitwise; DESIGN.md
§11). Only the per-worker gradient pass needs whole workers, and the
mesh step obtains them GATHER-FREE by splitting the WORKER axis instead
of replicating the model: with S shards and Wp = S·ceil(W/S) (worker
rows zero-padded to divisibility), shard s owns worker block
[s·Wb, (s+1)·Wb) and (``worker_split_grads``)

* a chunk-scheduled ``all_to_all`` (spec.chunk_plan — leaf x window
  chunks capped at ``max_chunk_cols``) trades its column window for its
  worker block's pieces, one chunk segment at a time. The plan cuts
  every segment at leaf boundaries, so each piece lies in one leaf (or
  the padding, dropped), and each leaf [Wb, *leaf_shape] is assembled
  from its own pieces in global column order;
* each worker is differentiated and clipped on its leaves — the flat
  pass's per-worker value_and_grad and clip
  (protocol._make_flat_local_pass over ``FlatSpec.unravel_leaves``) on
  W/S workers, optionally with the model's layer-level checkpoint
  (``remat=True``); the clip norm is the root of the sum of per-leaf
  sums of squares;
* the reverse ``all_to_all`` takes each piece cut straight from its
  clipped leaf into the owning shard's window (the reduce in
  reduce-scatter is a no-op here: worker-split grads are disjoint,
  never summed), and the O(d) dp_mix round stays fully local as before.

No worker's [Wb, d] row is built: with one worker a device it is
[1, d], which the TPU tiles one sublane deep, and every pass over it ran
far under HBM speed. A segment whose ends and leaves are lane-aligned
travels as [S, Wb·k, 128], so its pieces stay in whole (8, 128) tiles.

Memory contract: the persistent buffer is d/S per device AND the round's
peak is ~(W·d)/S per device — the Wb workers' leaves, their gradients,
plus transients bounded by the chunk budget (~W·max_chunk_cols elements
per collective) — and the activations of the grad pass on top (one
input per layer and the one layer being differentiated under
``remat``). No full [W, d] materialization exists anywhere in the
sharded program (statically enforced: repro.analysis's ``gather``
checker ERRORs on any full-width all_gather of the buffer). Compute also
drops to W/S grad-pass workers per device — on a single-socket host the
sharded round therefore WINS throughput instead of paying an S-fold
redundant gather (BENCH_shard.json).

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

The logical mode reproduces the unsharded round bitwise on the real
columns (CPU), because every column's arithmetic is independent, the
noise stream is counter-addressed and its gradient pass is the
unsharded row pass. The mesh mode draws the same noise bitwise, but its
clip norm sums per-leaf sums where the row pass sums once, so it agrees
with the unsharded round to float32 rounding; mesh rounds agree with
each other bitwise across chunk budgets (tests/test_shard.py).
"""
from __future__ import annotations

import bisect
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import protocol as protocol_lib
from repro.core.exchange import FlatSpec
from repro.kernels.dp_mix import ops as mix_ops
from repro.shard.layout import LANES, ShardLayout


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
    """The logical mode's gradient pass on the PADDED buffer: run the
    exact unsharded subprogram on the canonical [:, :d] view of all W
    rows, re-pad the gradients with exact zeros (padding columns carry no
    parameters, so their gradient IS zero). ``remat`` turns on the
    model's layer-level checkpoint (``cfg.remat``): each layer's
    activations are recomputed in the backward pass, so only the layer
    inputs are saved — activation memory for the price of a second
    forward."""
    base = protocol_lib._make_flat_local_pass(cfg, proto, spec.unravel_row,
                                              remat=remat)
    d, width = spec.d, spec.width

    def local_grads(flat_full, batch):
        losses, g, gnorms = base(flat_full[:, :d], batch)
        if width > d:
            g = jnp.pad(g, ((0, 0), (0, width - d)))
        return losses, g, gnorms

    return local_grads


def _piece_map(spec: FlatSpec, segs):
    """Where each ``all_to_all`` piece lives in the worker's leaves.

    Piece (s, j) is window s's span of segment j — global columns
    [s·shard_width + a_j, s·shard_width + b_j). The chunk plan cuts every
    segment at leaf boundaries, so each piece lies inside ONE leaf or in
    the padding past d. Returns ``(where, lane)``: ``where[s][j]`` is
    (leaf, column offset inside the leaf), or None for padding;
    ``lane[j]`` says segment j travels as a [.., k, LANES] view — its
    ends are lane-aligned and so is every leaf it touches, so each piece
    is a run of whole 128-column rows of its leaf's [k_leaf, LANES] view
    (the chip's (8, 128) tiles stay filled even with one worker a
    device)."""
    S, sw, d = spec.n_shards, spec.layout.shard_width, spec.d
    offs, sizes = spec.leaf_offsets(), spec.leaf_sizes()
    aligned = [o % LANES == 0 and n % LANES == 0
               for o, n in zip(offs, sizes)]
    where = []
    for s in range(S):
        row = []
        for a, b in segs:
            g0 = s * sw + a
            if g0 >= d:
                row.append(None)
                continue
            leaf = bisect.bisect_right(offs, g0) - 1
            row.append((leaf, g0 - offs[leaf]))
        where.append(row)
    lane = [a % LANES == 0 and b % LANES == 0
            and all(where[s][j] is None or aligned[where[s][j][0]]
                    for s in range(S))
            for j, (a, b) in enumerate(segs)]
    return where, lane


def _gather_block_leaves(flat_p, axis: str, spec: FlatSpec, segs, pieces):
    """Worker-split gather, leaf-addressed: trade this shard's [Wp,
    shard_width] column slab for its worker BLOCK's leaves, each [Wb,
    *leaf_shape], one chunk segment per ``all_to_all``. The segment is
    viewed as [S, Wb·k, LANES] (the S worker blocks leading, no unit
    axis) and the collective splits and concatenates that leading axis,
    so piece s of the result is window s's span of the block's rows and
    the trailing [Wb·k, LANES] dims stay whole tiles. Each collective
    moves one segment — the transient is [Wb, S·seg] elements, bounded
    by the chunk budget — and every leaf is assembled from its own
    pieces in global column order; padding pieces are dropped. No
    [Wb, width] row exists."""
    S = spec.n_shards
    Wb = flat_p.shape[0] // S
    where, lane = pieces
    recv, tails = [], []
    for j, (a, b) in enumerate(segs):
        tail = ((b - a) // LANES, LANES) if lane[j] else (b - a,)
        x = flat_p[:, a:b].reshape((S, Wb * tail[0]) + tail[1:])
        recv.append(jax.lax.all_to_all(x, axis, split_axis=0,
                                       concat_axis=0, tiled=True))
        tails.append(tail)
    parts = [[] for _ in spec.leaf_sizes()]
    for s in range(S):
        for j, at in enumerate(where[s]):
            if at is not None:
                parts[at[0]].append(
                    (lane[j], recv[j][s].reshape((Wb,) + tails[j])))
    leaves = []
    for shape, ps in zip(spec.leaf_shapes(), parts):
        tiled = all(t for t, _ in ps)
        xs = [p if tiled else p.reshape(Wb, -1) for _, p in ps]
        x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)
        leaves.append(x.reshape((x.shape[0],) + tuple(shape)))
    return leaves


def _scatter_grad_leaves(g_leaves, axis: str, spec: FlatSpec, segs, pieces):
    """The reverse chunk schedule: the worker block's [Wb, *leaf_shape]
    gradient leaves -> every worker's [Wp, shard_width] gradient columns
    on the OWNING shard. Each piece is cut straight from its leaf (padding
    pieces are zeros) and the S pieces of a segment are stacked window-
    major. Worker-split gradients are disjoint across devices, so the
    reduce of a reduce-scatter is a no-op and the scatter is the inverse
    ``all_to_all`` (pieces split per window, worker blocks concatenated
    back in order) — pure data movement, bitwise whatever the segment
    partition."""
    S = spec.n_shards
    where, lane = pieces
    Wb = g_leaves[0].shape[0]
    dt = g_leaves[0].dtype
    outs = []
    for j, (a, b) in enumerate(segs):
        n = b - a
        cut = []
        for s in range(S):
            at = where[s][j]
            if at is None:
                cut.append(jnp.zeros((Wb, n // LANES, LANES) if lane[j]
                                     else (Wb, n), dt))
            elif lane[j]:
                o = at[1] // LANES
                cut.append(g_leaves[at[0]].reshape(Wb, -1, LANES)[
                    :, o:o + n // LANES])
            else:
                cut.append(g_leaves[at[0]].reshape(Wb, -1)[
                    :, at[1]:at[1] + n])
        x = jnp.stack(cut)
        x = x.reshape((S, Wb * x.shape[2]) + x.shape[3:])
        y = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        outs.append(y.reshape(S * Wb, n))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def worker_split_grads(cfg, proto, spec: FlatSpec, axis: str, *,
                       remat: bool = False):
    """The gather-free worker-split grad pass of the mesh mode, for a
    ``shard_map`` body over ``axis``:

        grads(flat, batch) -> (losses [W], g_own [W, shard_width],
                               gnorms [W])

    ``flat`` is this shard's [W, shard_width] column slab and ``batch``
    every worker's batch (replicated). The shard trades its slab for its
    worker block's leaves (one chunk segment per collective),
    differentiates and clips each worker on its leaves — the flat pass's
    per-worker ``value_and_grad`` and clip, on a list of leaves instead of
    a row — and cuts the gradient columns straight from the clipped
    leaves back to their owning windows. Neither a [W, padded_width]
    replica nor a worker's [Wb, d] row ever exists. ``losses`` and the
    pre-clip ``gnorms`` are every worker's, gathered."""
    leaf_grads = protocol_lib._make_flat_local_pass(
        cfg, proto, spec.unravel_leaves, remat=remat)
    segs = spec.chunk_plan.exec_segments()
    pieces = _piece_map(spec, segs)
    Wn = proto.n_workers
    Wb = -(-Wn // spec.n_shards)
    Wp = Wb * spec.n_shards

    def grads(flat, batch):
        idx = jax.lax.axis_index(axis)
        with jax.named_scope("dwfl.shard"):
            fl_p = flat if Wp == Wn else jnp.pad(flat,
                                                 ((0, Wp - Wn), (0, 0)))
            leaves = _gather_block_leaves(fl_p, axis, spec, segs, pieces)

        def _block(a):
            # zero-pad the worker axis BEFORE slicing: a clamped
            # dynamic_slice on the last device would misalign the real
            # rows against the padded flat blocks.
            if Wp > Wn:
                a = jnp.pad(a, [(0, Wp - Wn)] + [(0, 0)] * (a.ndim - 1))
            return jax.lax.dynamic_slice_in_dim(a, idx * Wb, Wb, axis=0)

        losses_b, g_leaves, gnorms_b = leaf_grads(
            leaves, jax.tree_util.tree_map(_block, batch))
        with jax.named_scope("dwfl.shard"):
            g_own = _scatter_grad_leaves(g_leaves, axis, spec, segs,
                                         pieces)[:Wn]
        with jax.named_scope("dwfl.telemetry"):
            losses = jax.lax.all_gather(losses_b, axis, axis=0,
                                        tiled=True)[:Wn]
            gnorms = jax.lax.all_gather(gnorms_b, axis, axis=0,
                                        tiled=True)[:Wn]
        return losses, g_own, gnorms

    return grads


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
    xspec = protocol_lib._mixing_spec(proto, dynamic=dynamic)
    local_grads = (_padded_local_grads(cfg, proto, spec, remat=remat)
                   if axis is None else
                   worker_split_grads(cfg, proto, spec, axis, remat=remat))
    gamma, eta = proto.gamma, proto.eta

    def run(flat, batch, key, chan_t=None, W_t=None):
        if dynamic:
            k_n, k_x = jax.random.split(key)
            ch = chan_t
        else:
            k_n, k_m, k_x = jax.random.split(key, 3)
            ch = chan
        col0 = None if axis is None else (
            jax.lax.axis_index(axis).astype(jnp.int32) * layout.shard_width)
        losses, g_own, gnorms = local_grads(flat, batch)
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
        # only: the leaf pass's gnorms sum per-leaf sums, XLA picks the
        # mean's reduction strategy per program, and the psum of
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
    device otherwise. On the canonical [:, :d] view (CPU): bitwise-equal
    to the unsharded step logically, equal to float32 rounding on a mesh
    (the clip norm sums per leaf; module docstring)."""
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
