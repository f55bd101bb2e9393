"""Pallas TPU kernel: the fused DWFL round — the protocol's entire O(d)
post-gradient pipeline in ONE HBM pass over the flat [N, d] parameter
buffer.

Fuses (per column block of the unified engine update, exchange.py):

    x   = p − γ g                                   local SGD step
    n/c = (amp/c)·𝒢,  m = σ_m·𝒢'                   on-chip DP + AWGN noise
    mix = W @ (x + n/c)                             [N,N]×[N,BD] MXU matmul
    out = x + η·listen·[mix + m_scale·m − x − self·(n/c)]

replacing the unfused chain (per-leaf PRNG tree_map → bucket concatenate →
einsum exchange → unravel): 3+ HBM passes and two threefry sweeps become
one pass. Gaussians come from inverse-CDF sampling (√2·erf⁻¹(2u−1), a
cheap rational polynomial — ~4× faster than Box-Muller's log/cos/sin on
CPU and MXU-friendly on TPU) over 24-bit uniforms in the OPEN interval
(0, 1), drawn from the Pallas TPU PRNG (pltpu.prng_seed /
prng_random_bits) seeded per (call, program).

Grid: (replicate, column block) over the flat buffer — one replicate for
a single network, the fleet's R under vmap. Each program handles the full
worker axis (all N rows of the buffer as it is: no pad, no slice) times
a BLOCK_D-column VMEM tile, the last one ragged, so the [Np, Np] mixing
matrix (Np: N padded to the f32 sublane multiple) stays resident;
``MAX_NP`` records how far N can grow before it no longer fits and
ops._pallas_block_d how wide a tile is at a given N (DESIGN.md §9).
All channel quantities (c, σ_m, per-worker amplitudes, the mixing matrix
itself) are runtime OPERANDS — one compiled kernel serves every fading /
geometry / churn realization with zero retraces.

Off-TPU the SAME math runs as a plain fused-jnp program
(``dp_mix_fused_jnp`` — the counter-hash generator substitutes the TPU
PRNG with identical statistics); the Pallas body itself remains executable
under interpret=True and is validated against the jnp lowering and ref.py
in tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128     # last-dim tile multiple (f32)
SUBLANES = 8    # worker-axis pad multiple (f32 sublane)
# The mix must run in full f32 on the MXU: the noise terms reach |n/c| ~ 70
# and cancel against the self-correction column, so one bf16 pass would
# leave ~0.3 of noise in every parameter. (XLA CPU's dot is f32 anyway.)
_F32 = jax.lax.Precision.HIGHEST


def _hash_bits(idx, seed):
    """Counter-based hash (interpret-mode PRNG): xorshift-mul mix."""
    x = (idx.astype(jnp.uint32) * jnp.uint32(2654435761)) ^ seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    x = x ^ (x >> 16)
    return x


def _normal_from_bits(bits):
    """uint32 -> standard normal f32 via the inverse CDF: the 24-bit count
    k maps to the symmetric lattice t = (k − (2²³ − ½))/2²³ — every point
    is EXACTLY representable in f32 (half-integer numerator ≤ 2²⁴, power-
    of-two denominator), so |t| ≤ 1 − 2⁻²⁴ strictly and erf⁻¹ never sees
    ±1 (the naive (k + ½)/2²⁴ lattice ROUNDS to 1.0 at the top point and
    erf⁻¹(1) = inf — one poisoned draw per ~16M). Tails truncate at
    ≈ 5.4σ, the resolution of any 24-bit inverse-CDF sampler."""
    # the 24-bit count goes through int32 (exact: < 2²⁴): Mosaic has no
    # uint32 -> f32 cast, and int32 -> f32 is the same value on every
    # backend, so the CPU counter-hash stream is bit-for-bit unchanged
    k = (bits >> 8).astype(jnp.int32)
    t = (k.astype(jnp.float32) - (float(1 << 23) - 0.5)) * (1.0 / (1 << 23))
    return math.sqrt(2.0) * jax.lax.erf_inv(t)


def _normal_pair_hash(shape, d_padded, col0, seed, row0=0):
    """Two INDEPENDENT standard-normal fields from the counter-hash
    generator (CPU path / interpret mode): element (i, j) of block column
    offset ``col0`` draws from global counters 2·idx and 2·idx+1.

    ``d_padded`` is the COUNTER stride between consecutive worker rows.
    When the flat buffer is sharded over a model axis (repro.shard), every
    shard passes the same canonical stride (ShardLayout.counter_width) and
    its own global ``col0``, so the per-shard streams tile the exact
    single-device stream — CPU shardings stay bitwise-comparable.
    ``row0`` is the analogous GLOBAL ROW offset for worker-axis sharding
    (repro.shard.worker): each worker shard generates noise only for its
    own rows, addressed by global counters, so the sharded streams tile
    the unsharded stream exactly as well."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = ((jnp.asarray(row0).astype(jnp.uint32) + rows)
           * jnp.asarray(d_padded).astype(jnp.uint32)
           + jnp.asarray(col0).astype(jnp.uint32) + cols)
    g1 = _normal_from_bits(_hash_bits(idx * jnp.uint32(2), seed))
    g2 = _normal_from_bits(_hash_bits(idx * jnp.uint32(2) + jnp.uint32(1),
                                      seed))
    return g1, g2


def _round_math(p, g, normal_pair, c, sigma_m, amp, selfs, mscale, listen, w,
                *, gamma, eta, noisy):
    """The fused-round arithmetic, shared verbatim by the Pallas kernel
    body and the jnp lowering. p, g: [N, BD]; w: [Np, Np] and the vector
    args [Np, 1] columns, Np the sublane multiple of N (padded rows and
    columns zero). The kernel reads the buffer's N rows as they are, the
    jnp lowering its padded view (N = Np): either way each operand of the
    mix is padded to Np rows here, so both contract the same [Np, 3Np]
    block operand in the same order. ``normal_pair`` lazily yields the
    two [N, BD] noise fields.

    The noisy branch is written as ONE block matmul

        [w | w − diag(self) | diag(m_scale·σ_m)] @ [x; n/c; 𝒢_m]

    which is algebraically the mix + self-correction + AWGN chain
    (w@(x+n/c) + m_scale·σ_m·𝒢_m − self·(n/c) = upd + x). Besides mapping
    the whole post-noise pipeline onto the MXU, the GEMM operands force
    XLA CPU to MATERIALIZE the two hash+erf_inv noise fields: the naive
    elementwise form fuses both chains into the consumer loop and crosses
    a kLoop-fusion performance cliff (~3-7x at sharded window widths —
    ``lax.optimization_barrier`` is stripped by the CPU backend, so the
    operand boundary is the only reliable materialization point)."""
    n, Np = p.shape[0], w.shape[0]

    def rows(v):                             # [n, BD] -> [Np, BD], zeros
        if n == Np:
            return v
        return jnp.concatenate(
            [v, jnp.zeros((Np - n, v.shape[1]), v.dtype)], axis=0)

    x = p - gamma * g
    if noisy:
        g_n, g_m = normal_pair()
        nf = (amp[:n] / c) * g_n             # n/c: pre-scaled DP noise
        eye = jnp.eye(Np, dtype=jnp.float32)
        blocks = jnp.concatenate(
            [w, w - eye * selfs, eye * (mscale * sigma_m)], axis=1)
        z3 = jnp.concatenate([rows(x), rows(nf), rows(g_m)], axis=0)
        upd_px = jnp.dot(blocks, z3, precision=_F32,
                         preferred_element_type=jnp.float32)[:n]
        return x + eta * listen[:n] * (upd_px - x)
    mixed = jnp.dot(w, rows(x), precision=_F32,
                    preferred_element_type=jnp.float32)[:n]
    return x + eta * listen[:n] * (mixed - x)


def _sparse_round_math(p, g, normal_pair, c, sigma_m, amp, selfs, mscale,
                       listen, idx, w, self_w, *, gamma, eta, noisy):
    """The fused-round arithmetic against a padded neighbor list
    (repro.net.sparse.SparseW): algebraically the same update as
    ``_round_math``'s dense block GEMM, with the [N,N]×[N,BD] contraction
    replaced by k row-gathers of the noised buffer —

        z   = x + n/c
        mix = self_w·z + Σ_s w[:,s]·z[idx[:,s]]        (k static slots)
        out = x + η·listen·[mix + m_scale·σ_m·𝒢_m − x − self·(n/c)]

    O(N·k·d) flops and an O(N·d) transient (z is materialized ONCE as the
    shared gather operand — the same forced-materialization role the dense
    GEMM operand plays on the XLA CPU backend). Padded slots self-point
    with zero weight, so they contribute exactly 0.0; summation runs in
    slot order, hence results are ULP-close (not bitwise) to the dense
    reference — the noise FIELDS themselves are bitwise identical (same
    counters). Vector args are [N, 1] columns; idx/w are [N, k]."""
    x = p - gamma * g

    def gather_mix(z):
        acc = self_w * z
        for s in range(idx.shape[1]):
            acc = acc + w[:, s:s + 1] * z[idx[:, s]]
        return acc

    if noisy:
        g_n, g_m = normal_pair()
        nf = (amp / c) * g_n
        upd_px = gather_mix(x + nf) + (mscale * sigma_m) * g_m - selfs * nf
        return x + eta * listen * (upd_px - x)
    return x + eta * listen * (gather_mix(x) - x)


def dp_mix_sparse_jnp(p2, g2, seed, off, scal, amp, selfs, mscale, listen,
                      idx, w, self_w, *, gamma, eta, noisy,
                      counter_width=None, row0=0):
    """Sparse-mixing lowering of the fused round (all backends lower this
    via XLA gathers; there is no separate Pallas body — the gather
    accumulation is already memory-bound and shape-static). Draws the
    SAME counter-hash noise as ``dp_mix_fused_jnp`` on the identically
    padded [Np, Dp] window — bitwise-equal fields, so the dense path stays
    the reference at small N. ``row0`` offsets the noise counters for
    worker-axis shards (repro.shard.worker)."""
    Np, Dp = p2.shape
    p = p2.astype(jnp.float32)
    g = g2.astype(jnp.float32)
    col = lambda v: v.reshape(Np, 1)
    normal_pair = lambda: _normal_pair_hash(
        (Np, Dp), Dp if counter_width is None else counter_width,
        off.reshape(-1)[0], seed.reshape(-1)[0], row0=row0)
    out = _sparse_round_math(p, g, normal_pair, scal[0], scal[1], col(amp),
                             col(selfs), col(mscale), col(listen),
                             idx, jnp.asarray(w, jnp.float32),
                             col(self_w.astype(jnp.float32)),
                             gamma=gamma, eta=eta, noisy=noisy)
    return out.astype(p2.dtype)


def _dp_mix_kernel(seed_ref, off_ref, scal_ref, vec_ref, w_ref, p_ref,
                   g_ref, out_ref, *, gamma, eta, noisy, d_padded,
                   interpret):
    r = pl.program_id(0)                     # replicate
    j = pl.program_id(1)                     # column block
    p = p_ref[...].astype(jnp.float32)       # [N, BD]
    g = g_ref[...].astype(jnp.float32)
    vecs = vec_ref[...]                      # [Np, 4]: amp, self, m_scale, listen
    col = lambda i: vecs[:, i:i + 1]

    def normal_pair():
        if interpret:
            # off_ref[r]: global column offset of this CALL's window
            # (repro.shard — 0 for the whole-buffer round); counters use
            # the canonical stride d_padded so shard streams tile the
            # single-device stream exactly.
            return _normal_pair_hash(p.shape, d_padded,
                                     off_ref[r] + j * p.shape[1],
                                     seed_ref[r])
        from jax.experimental.pallas import tpu as pltpu
        # hash-mix the GLOBAL block index into the seed (NOT seed + pid:
        # with a ~1000-program grid, additive seeding lets nearby round
        # seeds reproduce bitwise-identical DP-noise blocks across
        # rounds/replicates, breaking the independent-Gaussian assumption
        # of the accounting). The block index counts from the window's
        # global column offset so sharded calls draw disjoint streams;
        # each replicate brings its own seed.
        blk = off_ref[r] // p.shape[1] + j
        pltpu.prng_seed(_hash_bits(blk, seed_ref[r]).astype(jnp.int32))
        b1 = pltpu.prng_random_bits(p.shape).astype(jnp.uint32)
        b2 = pltpu.prng_random_bits(p.shape).astype(jnp.uint32)
        return _normal_from_bits(b1), _normal_from_bits(b2)

    out = _round_math(p, g, normal_pair, scal_ref[2 * r], scal_ref[2 * r + 1],
                      col(0), col(1), col(2), col(3),
                      w_ref[...].astype(jnp.float32),
                      gamma=gamma, eta=eta, noisy=noisy)
    out_ref[...] = out.astype(out_ref.dtype)


# Scoped-VMEM limit the dense kernel compiles under: v5e has 128 MiB of
# VMEM per core, and Mosaic's default scoped limit (16 MiB) would cap the
# resident W well below the ceilings in MAX_NP.
VMEM_LIMIT = 64 * 1024 * 1024

# {noisy: largest padded worker count Np that compiles} for v5e under
# VMEM_LIMIT at column tiles of CEILING_BLOCK_D lanes (DESIGN.md §9). The
# resident W and, on the noisy path, the [Np, 3Np] block operand grow as
# Np²; each ceiling is the last Np that compiles at a 512-column tile, one
# sublane step (8) below the first that runs out of VMEM — pinned against
# the compiler by tests/test_tpu_compile.py. Below the ceiling the tile
# widens at constant area (ops._pallas_block_d), up to BLOCK_D_CAP lanes.
MAX_NP = {True: 1024, False: 1504}
CEILING_BLOCK_D = 512
# Widest column tile: the width with the shortest kernel time in a sweep of
# {4096, 8192, 16384, 32768} on a v5e at N = 10, d = 855,050 (PERF.md §6).
BLOCK_D_CAP = 16384


def _pallas_batched(seed, off, scal, vecs, W, p, g, *, gamma, eta, noisy,
                    block_d, d_padded, interpret):
    """The kernel over a leading replicate axis R: grid (R, cdiv(d, BD)).
    The per-replicate scalars (seed, column offset, c, σ_m) sit whole in
    SMEM and are indexed by the replicate program id; the [R, Np, 4]
    vectors, [R, Np, Np] W and [R, N, d] buffers are blocked per replicate.
    The buffers are read and written as they are: a tile spans all N rows
    (the full dimension), and the last column tile may overhang d — its
    columns past d are never written back, and no column reads another."""
    from jax.experimental.pallas import tpu as pltpu
    R, N, d = p.shape
    Np = W.shape[1]
    kernel = functools.partial(
        _dp_mix_kernel, gamma=gamma, eta=eta, noisy=noisy,
        d_padded=d_padded, interpret=interpret)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((None, N, block_d), lambda r, i: (r, 0, i))
    return pl.pallas_call(
        kernel,
        grid=(R, pl.cdiv(d, block_d)),
        in_specs=[
            smem, smem, smem,                                   # seed, off, scal
            pl.BlockSpec((None, Np, 4), lambda r, i: (r, 0, 0)),   # vecs
            # W: one buffer — it changes only between replicates
            pl.BlockSpec((None, Np, Np), lambda r, i: (r, 0, 0),
                         pipeline_mode=pl.Buffered(1)),
            tile, tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # the round updates the buffer in place: each tile writes only the
        # columns it read, so the output may take p's buffer, and a caller
        # whose p dies here (the scan body) needs no copy of it for the call
        input_output_aliases={5: 0},
        name="dp_mix_round",
    )(seed, off, scal.reshape(-1), vecs, W, p, g)


def _replicated_kernel(**statics):
    """``batched(seed[R], off[R], scal[R, 2], vecs[R, Np, 4], W[R, Np, Np],
    p[R, N, d], g[R, N, d])`` under a custom vmap rule: a vmapped
    caller (the fleet's replicate axis) folds its axis into R instead of
    letting pallas_call's generic batching block the 1-D operands, which
    the TPU lowering refuses. Nested vmaps fold into R the same way."""

    @jax.custom_batching.custom_vmap
    def batched(*args):
        return _pallas_batched(*args, **statics)

    @batched.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        R = args[0].shape[1]
        out = batched(*[a.reshape((axis_size * R,) + a.shape[2:])
                        for a in args])
        return out.reshape((axis_size, R) + out.shape[1:]), True

    return batched


def dp_mix_2d(p, g, seed, off, scal, amp, selfs, mscale, listen, W, *,
              gamma, eta, noisy, block_d, counter_width=None,
              interpret=True):
    """Pallas entry point. p, g: the [N, d] buffers as they are — no pad,
    no slice: the grid is cdiv(d, block_d) column tiles of all N rows,
    the last one ragged. Vector operands are [Np] and W is [Np, Np], Np
    the sublane multiple of N (zero-padded); ``scal`` = [c, σ_m]; ``off``
    the [1] int32 global column offset of this window (0 for the whole
    buffer) and ``counter_width`` the canonical noise-counter stride of
    the interpreter's draws (defaults to roundup(d, LANES), the jnp
    lowering's, so the two draw the same fields). Returns the updated
    [N, d] buffer (same dtype as p). Under vmap the replicate axis becomes
    the kernel's own grid axis (``_replicated_kernel``)."""
    d = p.shape[1]
    batched = _replicated_kernel(
        gamma=gamma, eta=eta, noisy=noisy, block_d=block_d,
        d_padded=(-(-d // LANES) * LANES if counter_width is None
                  else counter_width),
        interpret=interpret)
    vecs = jnp.stack([amp, selfs, mscale, listen], axis=-1)
    out = batched(seed.reshape(1), off.reshape(1), scal.reshape(1, 2),
                  vecs[None], W[None], p[None], g[None])
    return out[0]


def dp_mix_fused_jnp(p2, g2, seed, off, scal, amp, selfs, mscale, listen, W,
                     *, gamma, eta, noisy, counter_width=None):
    """The CPU lowering: identical arithmetic and identical counter-hash
    noise to the interpret-mode kernel run as ONE program (grid=1), minus
    the Pallas interpreter overhead — bitwise the same draws, so the two
    paths cross-validate (tests/test_kernels.py). ``off``/``counter_width``
    as in :func:`dp_mix_2d` (the repro.shard column-window hooks)."""
    Np, Dp = p2.shape
    p = p2.astype(jnp.float32)
    g = g2.astype(jnp.float32)
    col = lambda v: v.reshape(Np, 1)
    normal_pair = lambda: _normal_pair_hash(
        (Np, Dp), Dp if counter_width is None else counter_width,
        off.reshape(-1)[0], seed.reshape(-1)[0])
    out = _round_math(p, g, normal_pair, scal[0], scal[1], col(amp),
                      col(selfs), col(mscale), col(listen),
                      jnp.asarray(W, jnp.float32),
                      gamma=gamma, eta=eta, noisy=noisy)
    return out.astype(p2.dtype)
