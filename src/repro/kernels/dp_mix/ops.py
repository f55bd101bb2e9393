"""Jitted wrappers for the dp_mix kernel: flat-buffer and MixPlan APIs.

``dp_mix_round`` consumes the persistent flat [N, d] parameter buffer
(exchange.flatten_worker_tree) directly — no per-round concatenate, no
per-leaf PRNG tree_map. Channel quantities are runtime operands, so one
compiled call serves every realization (zero retraces — asserted by the
``dp_mix/retrace`` kernel-bench case). Implementation dispatch (``impl``):
the Pallas kernel on TPU, its bitwise-equivalent fused-jnp lowering on
CPU, and the Pallas interpreter on demand for kernel validation.

Dtype contract: the output buffer has the INPUT
buffer's dtype — internal arithmetic is f32, results cast back once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dp_mix import dp_mix as K


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lowering(sparse: bool = False) -> str:
    """What a round with ``impl=None`` runs on this backend: "pallas"
    (the dense kernel, TPU), "jnp" (its fused-jnp lowering elsewhere) or
    "xla-gather" (the sparse neighbor-list round, every backend)."""
    if sparse:
        return "xla-gather"
    return "pallas" if _on_tpu() else "jnp"


def _roundup(n: int, m: int) -> int:
    return -(-n // m) * m


def _pallas_block_d(Np: int, d: int, noisy: bool, counter_width=None) -> int:
    """The TPU column tile, from the shape: as wide as the VMEM the ceiling
    ``MAX_NP`` was measured in allows — a tile of Np rows holds at most the
    area of the ceiling's (MAX_NP[noisy], CEILING_BLOCK_D) tile — capped at
    ``K.BLOCK_D_CAP`` lanes, never below CEILING_BLOCK_D, and never wider
    than the buffer. At Np = 16 that is the cap; at the ceiling it is 512.
    For a sharded window (counter_width set) it is the widest multiple of
    128 within that bound that DIVIDES the window width, so the global
    block index (col0 // block_d + pid) tiles without collisions across
    shards."""
    area = K.MAX_NP[noisy] * K.CEILING_BLOCK_D
    lanes = max(min(K.BLOCK_D_CAP, area // Np),
                K.CEILING_BLOCK_D) // K.LANES
    if counter_width is not None and d % K.LANES == 0:
        n = d // K.LANES
        return K.LANES * max(c for c in range(1, lanes + 1) if n % c == 0)
    return K.LANES * min(lanes, -(-d // K.LANES))


def seed_from_key(key) -> jnp.ndarray:
    """PRNG key → int32 scalar kernel seed (traced; works for typed keys
    and raw uint32 key arrays)."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(key)
    except TypeError:  # pragma: no cover - exotic key reprs
        pass
    return key.reshape(-1)[-1].astype(jnp.int32)


def _pad_vec(v, N, Np, fill=0.0):
    v = jnp.asarray(v, jnp.float32)
    if v.ndim == 0:
        v = jnp.full((N,), v, jnp.float32)
    return jnp.pad(v, (0, Np - N), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("gamma", "eta", "noisy",
                                             "block_d", "impl",
                                             "counter_width"))
def dp_mix_round(p, g, seed, W, amp, c, sigma_m, *, gamma: float, eta: float,
                 self_scale=None, m_scale=None, listen=None,
                 noisy: bool = True, block_d=None, impl=None,
                 col0=0, counter_width=None):
    """One fused DWFL round over the flat buffer.

    p, g: [N, d] (params / clipped grads, any float dtype — preserved).
    seed: int32 scalar (see ``seed_from_key``). W: [N, N] mixing matrix.
    amp: [N] DP-noise amplitude |h_k|√(β_k P_k)·σ (exchange.mix_noise_amp).
    c / sigma_m: alignment constant and AWGN std (scalars, may be traced).
    self_scale / m_scale / listen: the unified-engine per-receiver vectors
    (defaults: full self-correction, complete-graph AWGN scaling
    1/(c·(N−1)), everyone listening). noisy=False skips the on-chip PRNG
    entirely (gossip).

    col0 / counter_width: the repro.shard column-window hooks. When the
    flat buffer is sharded over a model axis, each shard calls this on its
    own [N, d_shard] slice with its global column offset ``col0`` (traced
    — may be lax.axis_index-derived) and the layout's canonical
    ``counter_width`` (static); the per-shard CPU noise streams then tile
    the exact single-device stream. Defaults (0, None) are the
    whole-buffer round.

    impl: None (auto — ``lowering()``: "pallas" on TPU, "jnp" elsewhere)
    | "pallas" |
    "pallas_interpret" (the Pallas body executed by the interpreter —
    slow; kernel-validation only) | "jnp" (the fused-jnp CPU lowering,
    bitwise-identical draws to "pallas_interpret").

    The Pallas kernel (and its interpreter) reads and writes p, g as they
    are: column tiles of all N rows, ``block_d`` wide (default: from the
    shape, ``_pallas_block_d``), the last one ragged; only W and the [N]
    vectors are padded to Np = roundup(N, 8). The jnp lowering runs one
    program on the padded [Np, Dp] view and slices the result back.
    """
    N, d = p.shape
    if impl is None:
        impl = lowering()
    Np = _roundup(N, K.SUBLANES)
    if impl == "pallas" and Np > K.MAX_NP[noisy]:
        # the resident [Np, Np] W no longer fits in VMEM (DESIGN.md §9):
        # refuse here rather than hand the compiler a kernel it cannot place
        raise ValueError(
            f"dense dp_mix at N={N} is past the kernel's VMEM ceiling "
            f"(N <= {K.MAX_NP[noisy]} {'with' if noisy else 'without'} "
            f"noise: the [N, N] mixing matrix is VMEM-resident); mix "
            f"through a sparse neighbor list (--sparse-neighbors) at this N")
    W2 = jnp.pad(jnp.asarray(W, jnp.float32), ((0, Np - N), (0, Np - N)))
    c = jnp.asarray(c, jnp.float32).reshape(())
    scal = jnp.stack([c, jnp.asarray(sigma_m, jnp.float32).reshape(())])
    amp2 = _pad_vec(amp, N, Np)
    selfs = _pad_vec(1.0 if self_scale is None else self_scale, N, Np)
    if m_scale is None:
        m_scale = jnp.full((N,), 1.0, jnp.float32) / (c * max(N - 1, 1))
    mscale = _pad_vec(m_scale, N, Np)
    # padded rows must stay exactly x (= 0): they don't listen
    lst = _pad_vec(1.0 if listen is None else listen, N, Np)
    seed = jnp.asarray(seed, jnp.int32).reshape(1)
    off = jnp.asarray(col0, jnp.int32).reshape(1)

    if impl == "jnp":
        # one program off-TPU (no grid to amortize), on the padded [Np, Dp]
        # view; the Pallas kernel reads and writes the [N, d] buffer as it is
        Dp = _roundup(d, block_d or K.LANES)
        pad = ((0, Np - N), (0, Dp - d))
        out = K.dp_mix_fused_jnp(jnp.pad(p, pad), jnp.pad(g, pad), seed, off,
                                 scal, amp2, selfs, mscale, lst, W2,
                                 gamma=gamma, eta=eta, noisy=noisy,
                                 counter_width=counter_width)
        return out[:N, :d]
    if block_d is None:
        block_d = _pallas_block_d(Np, d, noisy, counter_width)
    return K.dp_mix_2d(p, g, seed, off, scal, amp2, selfs, mscale, lst, W2,
                       gamma=gamma, eta=eta, noisy=noisy, block_d=block_d,
                       counter_width=counter_width,
                       interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("gamma", "eta", "noisy",
                                             "block_d", "impl",
                                             "counter_width"))
def dp_mix_round_sparse(p, g, seed, sw, amp, c, sigma_m, *, gamma: float,
                        eta: float, self_scale=None, m_scale=None,
                        listen=None, noisy: bool = True, block_d=None,
                        impl=None, col0=0, counter_width=None):
    """One fused DWFL round mixed through a padded neighbor list
    (repro.net.sparse.SparseW) — O(N·k·d) instead of the dense O(N²·d).

    Same contract as :func:`dp_mix_round` with ``sw`` replacing ``W``.
    The [Np, Dp] padding, counter stride, ``col0``/``counter_width``
    window hooks, and the seed→counter mapping are IDENTICAL to the dense
    jnp lowering, so both paths draw bitwise-equal noise fields and the
    dense round remains the small-N reference (sparse results differ only
    by slot-order summation ULPs — tests/test_sparse.py). It runs as an XLA
    gather on every backend, TPU included: there is no Pallas body (see
    dp_mix.dp_mix_sparse_jnp), so ``impl`` accepts "jnp"/None and refuses
    a Pallas request rather than silently running the gather instead.
    """
    N, d = p.shape
    if impl not in (None, "jnp"):
        raise NotImplementedError(
            f"sparse dp_mix runs as an XLA gather and has no {impl!r} "
            f"lowering; use impl=None")
    Np = _roundup(N, K.SUBLANES)
    if block_d is None:
        block_d = _roundup(d, K.LANES)
    Dp = _roundup(d, block_d)

    p2 = jnp.pad(p, ((0, Np - N), (0, Dp - d)))
    g2 = jnp.pad(g, ((0, Np - N), (0, Dp - d)))
    # padded rows: self-pointing zero-weight slots, zero self weight —
    # they neither listen (listen pads 0) nor perturb real rows (no real
    # row gathers an index ≥ N)
    idx2 = jnp.pad(jnp.asarray(sw.idx, jnp.int32), ((0, Np - N), (0, 0)),
                   constant_values=0)
    w2 = jnp.pad(jnp.asarray(sw.w, jnp.float32), ((0, Np - N), (0, 0)))
    self_w2 = _pad_vec(sw.self_w, N, Np)
    c = jnp.asarray(c, jnp.float32).reshape(())
    scal = jnp.stack([c, jnp.asarray(sigma_m, jnp.float32).reshape(())])
    amp2 = _pad_vec(amp, N, Np)
    selfs = _pad_vec(1.0 if self_scale is None else self_scale, N, Np)
    if m_scale is None:
        m_scale = jnp.full((N,), 1.0, jnp.float32) / (c * max(N - 1, 1))
    mscale = _pad_vec(m_scale, N, Np)
    lst = _pad_vec(1.0 if listen is None else listen, N, Np)
    seed = jnp.asarray(seed, jnp.int32).reshape(1)
    off = jnp.asarray(col0, jnp.int32).reshape(1)

    out2 = K.dp_mix_sparse_jnp(p2, g2, seed, off, scal, amp2, selfs, mscale,
                               lst, idx2, w2, self_w2, gamma=gamma, eta=eta,
                               noisy=noisy, counter_width=counter_width)
    return out2[:N, :d].astype(p.dtype)


def dp_mix_round_plan(p, g, seed, plan, *, gamma: float, eta: float,
                      impl=None, col0=0, counter_width=None):
    """MixPlan front end (exchange.plan_* → one fused round). Dispatches
    on the plan's W: a dense [N, N] array runs the dense kernel, a
    repro.net.sparse.SparseW neighbor list runs the O(N·k) sparse round
    (an XLA gather: a Pallas ``impl`` is refused there). Everything it
    runs (the kernel and its small operands; off-TPU the pads to
    [Np, Dp] and the slice back) sits in the ``dwfl.mix`` named scope."""
    from repro.net.sparse import SparseW
    mix = dp_mix_round_sparse if isinstance(plan.W, SparseW) else dp_mix_round
    with jax.named_scope("dwfl.mix"):
        return mix(p, g, seed, plan.W, plan.amp, plan.c, plan.sigma_m,
                   gamma=gamma, eta=eta, self_scale=plan.self_scale,
                   m_scale=plan.m_scale, listen=plan.listen,
                   noisy=plan.noisy, impl=impl, col0=col0,
                   counter_width=counter_width)
