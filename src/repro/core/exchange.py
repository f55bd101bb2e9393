"""Unified mixing-matrix exchange engine — Eqt. (8) as ONE primitive.

The paper states every DWFL round in matrix form,

    X ← (X − γG)Ψ + Φ(Ψ − I),        Ψ = (1 − η)I + ηW,

and every exchange variant the repo grew (complete graph, ring/torus,
dynamic geometry/churn, sampled participation, the orthogonal and
centralized baselines, noiseless gossip) is an instance of the one
receiver-side update

    x_i ← x_i + η·listen_i · [ Σ_k W_ik (x_k + n_k/c) + m̃_i
                               − x_i − self_i · n_i/c ]

parameterized by a mixing matrix ``W`` and three per-receiver vectors:

    ============  =====================================  ==================
    scheme        W                                      self / m̃ / listen
    ============  =====================================  ==================
    dwfl          ((1) − I)/(N−1)  (complete graph)      1 / m/(c(N−1)) / 1
    ring/torus    repro.core.topology W                  1 / m/(c·deg)  / 1
    dynamic       net Metropolis/masked-complete W_t     1 / m/(c·deg)  / deg>0
    sampled       W_ik = p_k(1−δ_ik)/max(n_tx−p_i, 1)    p / m/(c·den)  / 1
    gossip        complete, σ = σ_m = 0                  1 / 0          / 1
    orthogonal    complete, c = 1, inv-gain noise        0 / link AWGN  / 1
    centralized   (1)/N, η = 1, shared PS AWGN           0 / m/(cN)     / 1
    ============  =====================================  ==================

``mix_exchange`` below implements that update once; ``ExchangeSpec``
entries build (or mask) the ``W`` and the vectors, and the protocol
dispatches through :func:`resolve_spec` instead of a scheme if/elif
ladder. Every spec is verified against ``dwfl.matrix_form_reference``
(extended to arbitrary doubly-stochastic W) in tests/test_exchange.py.

The same plans feed the fused Pallas kernel family
``repro.kernels.dp_mix`` (local SGD step + on-chip DP noise + the
[N,N]×[N,d] mixing matmul + self-correction + AWGN in one HBM pass over
the persistent flat [N, d] parameter buffer — see ``FlatSpec`` /
``MixPlan`` and protocol.make_flat_train_step). The baselines have no
plan: the flat round runs their ``run`` on the [N, d] buffer as one leaf.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Tree = object  # pytree alias


# ---------------------------------------------------------------------------
# noise generation
# ---------------------------------------------------------------------------


def _leaf_keys(key, tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def _normal(key, shape) -> jnp.ndarray:
    """Standard normals [shape] f32, held apart from the amplitude that
    scales them: XLA would otherwise fold a static amplitude into the
    sampler's constants in some programs and not in others (a scan body
    against a plain jit), and the same draw would round differently by
    an ULP or two, breaking chunk invariance."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def dp_noise(key, X: Tree, chan) -> Tree:
    """n_k = |h_k| sqrt(β_k P_k) * 𝒢_k,  𝒢_k ~ N(0, σ²) i.i.d per entry.

    X leaves are worker-stacked [W, ...]; the per-worker amplitude
    broadcasts along the leading axis. ``chan`` may be the static
    ChannelState (amplitudes are compile-time constants) or a traced
    net.TracedChannelState (amplitudes are runtime arrays).
    """
    scale = mix_noise_amp(chan)

    def one(k, x):
        amp = scale.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
        return (amp * _normal(k, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map(one, _leaf_keys(key, X), X)


def channel_noise(key, X: Tree, sigma_m) -> Tree:
    """m_i ~ N(0, σ_m²) per receiver (leading axis) per entry."""
    def one(k, x):
        return (sigma_m * _normal(k, x.shape)).astype(x.dtype)
    return jax.tree_util.tree_map(one, _leaf_keys(key, X), X)


def mix_noise_amp(chan) -> jnp.ndarray:
    """Per-worker over-the-air DP-noise amplitude |h_k|√(β_k P_k)·σ ([N]) —
    the noise scale the fused dp_mix kernel generates on-chip. Accepts the
    static ChannelState or the traced net.TracedChannelState (the
    net → kernels handoff)."""
    return (jnp.asarray(chan.noise_scale, jnp.float32)
            * jnp.asarray(chan.dp_sigma, jnp.float32))


# ---------------------------------------------------------------------------
# W constructors (the taxonomy table above)
# ---------------------------------------------------------------------------


def complete_W(N: int) -> jnp.ndarray:
    """The paper's W = ((1)_N − I)/(N − 1)."""
    return (jnp.ones((N, N), jnp.float32)
            - jnp.eye(N, dtype=jnp.float32)) / (N - 1)


def masked_complete_W(mask: jnp.ndarray) -> jnp.ndarray:
    """Masked complete-graph mixing: active workers average over the other
    active workers (exactly the paper's W = ((1)−I)/(N−1) when everyone is
    on), inactive workers get the identity row. Symmetric, doubly
    stochastic for ≥ 2 active workers. (Traced — repro.net churn path.)"""
    p = jnp.asarray(mask, jnp.float32)
    n = p.shape[0]
    n_act = jnp.maximum(jnp.sum(p), 2.0)
    off = p[:, None] * p[None, :] * (1.0 - jnp.eye(n, dtype=jnp.float32))
    W = off / (n_act - 1.0)
    return W + jnp.diag(1.0 - jnp.sum(W, axis=1))


def sampled_W(participate) -> tuple:
    """Per-round participation mixing (privacy amplification by
    subsampling): receiver i averages the transmitters it can hear,
    W_ik = p_k(1−δ_ik)/max(n_tx − p_i, 1). Row-stochastic whenever ≥ 2
    workers transmit (the protocol's guaranteed pair). Returns
    (W, p, denom): ``p`` doubles as the self-correction mask (a worker
    subtracts its own DP noise only in rounds it transmitted) and
    ``denom`` scales the receiver AWGN."""
    p = jnp.asarray(participate, jnp.float32)
    N = p.shape[0]
    n_tx = jnp.maximum(jnp.sum(p), 2.0)
    denom = jnp.maximum(n_tx - p, 1.0)                      # [N]
    W = (p[None, :] * (1.0 - jnp.eye(N, dtype=jnp.float32))) / denom[:, None]
    return W, p, denom


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def mix_exchange(X: Tree, noise_n: Tree, noise_m: Tree, c, eta, W, *,
                 self_scale=None, m_scale=None, listen=None) -> Tree:
    """One mixing-matrix parameter exchange over worker-stacked leaves:

        x_i ← x_i + η·listen_i [ Σ_k W_ik (x_k + n_k/c) + m_scale_i·m_i
                                 − x_i − self_scale_i·n_i/c ]

    ``W`` [N, N] and the optional per-receiver vectors may be static numpy
    or traced jnp arrays — one compiled call serves every realization.
    ``self_scale``/``listen`` default to 1 (full self-correction, every
    receiver listening); ``m_scale`` defaults to 1 (noise_m pre-scaled).
    All arithmetic is f32; leaves are cast back to their own dtype.
    """
    Wj = jnp.asarray(W, jnp.float32)
    N = Wj.shape[0]

    def _vec(v, n_lead, ndim):
        """Per-receiver vector → broadcastable [n_lead, 1, ...] (scalars
        pass through — they broadcast as-is)."""
        if v is None:
            return None
        v = jnp.asarray(v, jnp.float32)
        if v.ndim == 0:
            return v
        return v.reshape((n_lead,) + (1,) * (ndim - 1))

    def one(x, n, m):
        xf = x.astype(jnp.float32)
        nf = n.astype(jnp.float32) / c
        # full f32 on the MXU: n/c cancels against the self-correction
        # term, which one bf16 pass (TPU's default) would not preserve
        mixed = jnp.einsum("ij,j...->i...", Wj, xf + nf,
                           precision=jax.lax.Precision.HIGHEST)
        selfs = _vec(self_scale, N, x.ndim)
        upd = mixed - xf - (nf if selfs is None else selfs * nf)
        if m is not None:
            mf = m.astype(jnp.float32)
            ms = _vec(m_scale, m.shape[0], m.ndim)
            upd = upd + (mf if ms is None else ms * mf)
        li = _vec(listen, N, x.ndim)
        if li is not None:
            upd = li * upd
        return (xf + eta * upd).astype(x.dtype)

    return jax.tree_util.tree_map(one, X, noise_n, noise_m)


# ---------------------------------------------------------------------------
# MixPlan — the (W, vectors) bundle shared by the jnp path and the fused
# dp_mix kernel (all fields static numpy or traced jnp; shapes fixed)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MixPlan:
    """Everything the fused dp_mix round needs beyond (params, grads):
    the mixing matrix, the per-receiver vectors of the unified update, and
    the channel noise amplitudes. ``noisy`` is a STATIC flag (gossip skips
    the on-chip PRNG entirely)."""
    W: jnp.ndarray                    # [N, N]
    c: jnp.ndarray                    # scalar alignment constant
    amp: jnp.ndarray                  # [N] DP-noise amplitude (incl. σ)
    sigma_m: jnp.ndarray              # scalar receiver AWGN std
    self_scale: Optional[jnp.ndarray] = None   # [N] own-noise correction mask
    m_scale: Optional[jnp.ndarray] = None      # [N] AWGN scaling m̃ = m_scale·m
    listen: Optional[jnp.ndarray] = None       # [N] row gate
    noisy: bool = True                # static: generate noise at all?


jax.tree_util.register_dataclass(
    MixPlan,
    data_fields=["W", "c", "amp", "sigma_m", "self_scale", "m_scale",
                 "listen"],
    meta_fields=["noisy"])


def _deg_scale(Wj, c):
    """m̃_i = m_i/(c·deg_i): receiver AWGN normalized by the neighborhood
    size (deg counts positive off-diagonal and diagonal entries alike,
    matching the historical per-variant formulas)."""
    deg = jnp.asarray((Wj > 0).sum(1), jnp.float32)
    return 1.0 / (c * jnp.maximum(deg, 1.0))


def plan_complete(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    N = chan.n_workers
    c = chan.c
    return MixPlan(W=complete_W(N), c=jnp.asarray(c, jnp.float32),
                   amp=mix_noise_amp(chan),
                   sigma_m=jnp.asarray(chan.awgn_sigma, jnp.float32),
                   m_scale=jnp.full((N,), 1.0, jnp.float32) / (c * (N - 1)))


def plan_gossip(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    N = chan.n_workers
    return MixPlan(W=complete_W(N), c=jnp.asarray(chan.c, jnp.float32),
                   amp=jnp.zeros((N,), jnp.float32),
                   sigma_m=jnp.zeros((), jnp.float32),
                   m_scale=jnp.zeros((N,), jnp.float32), noisy=False)


def plan_topology(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    Wj = jnp.asarray(proto.mixing_matrix() if W_arg is None else W_arg,
                     jnp.float32)
    return MixPlan(W=Wj, c=jnp.asarray(chan.c, jnp.float32),
                   amp=mix_noise_amp(chan),
                   sigma_m=jnp.asarray(chan.awgn_sigma, jnp.float32),
                   m_scale=_deg_scale(Wj, chan.c))


def plan_dynamic(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    """Traced per-round W from repro.net: workers with no active neighbor
    (churned out, or isolated by the interference graph: W row = e_i) take
    NO update this round — they neither hear the superposition nor its
    AWGN."""
    Wj = jnp.asarray(W_arg, jnp.float32)
    off_deg = jnp.sum((Wj > 0) & ~jnp.eye(Wj.shape[0], dtype=bool), axis=1)
    listen = (off_deg > 0).astype(jnp.float32)
    deg = jnp.maximum(off_deg.astype(jnp.float32), 1.0)
    return MixPlan(W=Wj, c=jnp.asarray(chan.c, jnp.float32),
                   amp=mix_noise_amp(chan),
                   sigma_m=jnp.asarray(chan.awgn_sigma, jnp.float32),
                   m_scale=1.0 / (chan.c * deg), listen=listen)


def plan_dynamic_sparse(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    """plan_dynamic for a padded neighbor list (repro.net.sparse.SparseW):
    the MixPlan carries the SparseW itself as ``W`` (it is a pytree, so the
    plan still flows through jit/scan unchanged) and derives the SAME
    listen/m_scale vectors the dense plan computes — off_degree counts the
    identical integers ``sum((W>0) & ~eye, 1)`` does, so the two plans are
    bitwise-equal everywhere except the W representation."""
    sw = W_arg
    off_deg = sw.off_degree()
    listen = (off_deg > 0).astype(jnp.float32)
    deg = jnp.maximum(off_deg, 1.0)
    return MixPlan(W=sw, c=jnp.asarray(chan.c, jnp.float32),
                   amp=mix_noise_amp(chan),
                   sigma_m=jnp.asarray(chan.awgn_sigma, jnp.float32),
                   m_scale=1.0 / (chan.c * deg), listen=listen)


def plan_sampled(proto, chan, k_x=None, W_arg=None) -> MixPlan:
    from repro.core import protocol as protocol_lib
    mask = W_arg if W_arg is not None else protocol_lib.sample_participation(
        k_x, proto.n_workers, proto.participation)
    W, p, denom = sampled_W(mask)
    return MixPlan(W=W, c=jnp.asarray(chan.c, jnp.float32),
                   amp=mix_noise_amp(chan),
                   sigma_m=jnp.asarray(chan.awgn_sigma, jnp.float32),
                   self_scale=p, m_scale=1.0 / (chan.c * denom))


# ---------------------------------------------------------------------------
# exchange runs — one per spec, all routed through mix_exchange
# ---------------------------------------------------------------------------


def mix_exchange_sparse(X: Tree, noise_n: Tree, noise_m: Tree, c, eta, sw, *,
                        self_scale=None, m_scale=None, listen=None) -> Tree:
    """:func:`mix_exchange` against a padded neighbor list
    (repro.net.sparse.SparseW): the [N,N] einsum becomes k row-gathers of
    the noised buffer — O(N·k·leaf) instead of O(N²·leaf), identical
    update otherwise (ULP-close: slot-order summation)."""
    N = sw.idx.shape[-2]

    def _vec(v, n_lead, ndim):
        if v is None:
            return None
        v = jnp.asarray(v, jnp.float32)
        if v.ndim == 0:
            return v
        return v.reshape((n_lead,) + (1,) * (ndim - 1))

    def one(x, n, m):
        xf = x.astype(jnp.float32)
        nf = n.astype(jnp.float32) / c
        z = xf + nf
        col = lambda v: v.reshape((N,) + (1,) * (x.ndim - 1))
        mixed = col(sw.self_w.astype(jnp.float32)) * z
        for s in range(sw.idx.shape[-1]):
            mixed = mixed + col(sw.w[:, s]) * z[sw.idx[:, s]]
        selfs = _vec(self_scale, N, x.ndim)
        upd = mixed - xf - (nf if selfs is None else selfs * nf)
        if m is not None:
            mf = m.astype(jnp.float32)
            ms = _vec(m_scale, m.shape[0], m.ndim)
            upd = upd + (mf if ms is None else ms * mf)
        li = _vec(listen, N, x.ndim)
        if li is not None:
            upd = li * upd
        return (xf + eta * upd).astype(x.dtype)

    return jax.tree_util.tree_map(one, X, noise_n, noise_m)


def run_mix(X, noise_n, noise_m, eta, plan: MixPlan) -> Tree:
    from repro.net.sparse import SparseW
    if isinstance(plan.W, SparseW):
        return mix_exchange_sparse(X, noise_n, noise_m, plan.c, eta, plan.W,
                                   self_scale=plan.self_scale,
                                   m_scale=plan.m_scale, listen=plan.listen)
    return mix_exchange(X, noise_n, noise_m, plan.c, eta, plan.W,
                        self_scale=plan.self_scale, m_scale=plan.m_scale,
                        listen=plan.listen)


def _run_complete(X, keys, chan, proto, *, W=None):
    k_n, k_m, k_x = keys
    n = dp_noise(k_n, X, chan)
    m = channel_noise(k_m, X, chan.awgn_sigma)
    return run_mix(X, n, m, proto.eta, plan_complete(proto, chan))


def _run_gossip(X, keys, chan, proto, *, W=None):
    zero = jax.tree_util.tree_map(jnp.zeros_like, X)
    return run_mix(X, zero, zero, proto.eta, plan_gossip(proto, chan))


def _run_topology(X, keys, chan, proto, *, W=None):
    k_n, k_m, k_x = keys
    n = dp_noise(k_n, X, chan)
    m = channel_noise(k_m, X, chan.awgn_sigma)
    return run_mix(X, n, m, proto.eta, plan_topology(proto, chan, W_arg=W))


def _run_dynamic(X, keys, chan, proto, *, W=None):
    k_n, k_m = keys[0], keys[1]
    n = dp_noise(k_n, X, chan)
    m = channel_noise(k_m, X, chan.awgn_sigma)
    return run_mix(X, n, m, proto.eta, plan_dynamic(proto, chan, W_arg=W))


def _run_dynamic_sparse(X, keys, chan, proto, *, W=None):
    k_n, k_m = keys[0], keys[1]
    n = dp_noise(k_n, X, chan)
    m = channel_noise(k_m, X, chan.awgn_sigma)
    return run_mix(X, n, m, proto.eta,
                   plan_dynamic_sparse(proto, chan, W_arg=W))


def _run_sampled(X, keys, chan, proto, *, W=None):
    k_n, k_m, k_x = keys
    n = dp_noise(k_n, X, chan)
    m = channel_noise(k_m, X, chan.awgn_sigma)
    return run_mix(X, n, m, proto.eta, plan_sampled(proto, chan, k_x))


# Floor for the inverted per-link gain |h_j|√(α_j P_j) in the orthogonal
# baseline: a deep-fade draw (|h_j| → 0) sends the gain to 0 and the
# inverted AWGN std to infinity, poisoning the whole round with inf/NaN.
# The clamp caps the noise inflation of any single link at 40 dB (power)
# below the best link — beyond that a real receiver would declare the link
# in outage rather than amplify pure noise.
ORTHOGONAL_GAIN_FLOOR = 1e-2   # amplitude ratio to the best link (= -40 dB power)


def run_orthogonal(X: Tree, key, chan, eta) -> Tree:
    """Orthogonal (pairwise digital-style) baseline: each link carries ONE
    sender's signal, masked only by that sender's own noise (constant-in-N
    privacy, Remark 4.1), plus per-link AWGN.

    In engine terms: complete-graph W over the gain-inverted signals
    x̂_j = x_j + (√β_j/√α_j)σ𝒢_j (noise already parameter-scale ⇒ c = 1),
    NO self-correction, and the per-link AWGN mean sampled directly
    (statistically identical, avoids the O(W²d) tensor). Communication:
    N-1 transmissions per worker per round vs DWFL's single superposed one.
    """
    N = chan.n_workers
    # sender-side effective noise after gain inversion (static channel only:
    # the host-side float math below bakes these in at trace time)
    inv_gain = jnp.asarray(
        np.sqrt(chan.beta / np.maximum(chan.alpha, 1e-9)) * chan.dp_sigma,
        jnp.float32)
    # per-link AWGN std after inversion, averaged over N-1 links; the
    # inverted gain is clamped (ORTHOGONAL_GAIN_FLOOR relative to the best
    # link) so one deep-fade |h| cannot blow the std up to inf
    gain = chan.h * np.sqrt(chan.alpha * chan.P)
    gain = np.maximum(gain, max(ORTHOGONAL_GAIN_FLOOR * float(np.max(gain)),
                                1e-30))
    link_std = chan.awgn_sigma / gain
    mean_m_std = float(np.sqrt(np.mean(link_std ** 2) / (N - 1)))

    # one split per leaf key, both halves sliced from the SAME pair —
    # splitting the key twice (once per half) derives duplicate lineage
    # from one parent, which the key-discipline checker (repro.analysis)
    # rightly flags as reuse; split() is deterministic, so this form
    # realizes bitwise-identical streams to the old double-split
    pairs = jax.tree_util.tree_map(jax.random.split, _leaf_keys(key, X))
    k1 = jax.tree_util.tree_map(lambda p: p[0], pairs)
    k2 = jax.tree_util.tree_map(lambda p: p[1], pairs)
    n = jax.tree_util.tree_map(
        lambda k, x: inv_gain.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
        * _normal(k, x.shape), k1, X)
    m = jax.tree_util.tree_map(
        lambda k, x: mean_m_std * _normal(k, x.shape), k2, X)
    return mix_exchange(X, n, m, 1.0, eta, complete_W(N), self_scale=0.0)


def run_centralized(X: Tree, noise_n: Tree, key, chan) -> Tree:
    """Centralized PS baseline (Seif et al. [11] style): all workers
    transmit over the MAC to a parameter server, which rescales and
    broadcasts the average — W = (1)/N (including self), η = 1, no
    self-correction, ONE shared AWGN draw at the PS scaled by 1/(cN)."""
    N = chan.n_workers
    c = chan.c
    m = jax.tree_util.tree_map(
        lambda k, x: jnp.asarray(chan.awgn_sigma, jnp.float32)
        * _normal(k, (1,) + x.shape[1:]),
        _leaf_keys(key, X), X)
    W = jnp.ones((N, N), jnp.float32) / N
    return mix_exchange(X, noise_n, m, c, 1.0, W,
                        self_scale=0.0, m_scale=1.0 / (c * N))


def _run_orthogonal_spec(X, keys, chan, proto, *, W=None):
    return run_orthogonal(X, keys[2], chan, proto.eta)


def _run_centralized_spec(X, keys, chan, proto, *, W=None):
    k_n, k_m, k_x = keys
    n = dp_noise(k_n, X, chan)
    return run_centralized(X, n, k_m, chan)


# ---------------------------------------------------------------------------
# ExchangeSpec + dispatch (replaces the scheme if/elif ladder)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ExchangeSpec:
    """One exchange variant: how to run a round on worker-stacked leaves
    (``run``) and how to build the fused-kernel plan (``plan`` — None for
    the orthogonal/centralized baselines outside the mixing family, whose
    ``run`` the flat round calls on the [W, d] buffer as one leaf).
    """
    name: str
    run: Callable
    plan: Optional[Callable] = None


SPECS = {
    "complete": ExchangeSpec("complete", _run_complete, plan=plan_complete),
    "gossip": ExchangeSpec("gossip", _run_gossip, plan=plan_gossip),
    "topology": ExchangeSpec("topology", _run_topology, plan=plan_topology),
    "dynamic": ExchangeSpec("dynamic", _run_dynamic, plan=plan_dynamic),
    "dynamic_sparse": ExchangeSpec("dynamic_sparse", _run_dynamic_sparse,
                                   plan=plan_dynamic_sparse),
    "sampled": ExchangeSpec("sampled", _run_sampled, plan=plan_sampled),
    "orthogonal": ExchangeSpec("orthogonal", _run_orthogonal_spec),
    "centralized": ExchangeSpec("centralized", _run_centralized_spec),
}


def resolve_spec(proto, dynamic: bool = False) -> ExchangeSpec:
    """Scheme/scenario → ExchangeSpec (the ONE routing policy; both the
    static and the dynamic train-step factories consult it). ``dynamic``:
    the per-round traced-W step (repro.net) — only scheme="dwfl" has
    dynamic semantics (the baselines are static-channel comparisons)."""
    if dynamic:
        if proto.scheme != "dwfl":
            raise ValueError(f"dynamic channel model requires scheme='dwfl', "
                             f"got {proto.scheme!r}")
        # sparse_neighbors > 0: the per-round W is a repro.net.sparse
        # SparseW neighbor list and mixing runs O(N·k)
        if getattr(proto, "sparse_neighbors", 0):
            return SPECS["dynamic_sparse"]
        return SPECS["dynamic"]
    if proto.scheme == "gossip":
        return SPECS["gossip"]
    if proto.scheme == "orthogonal":
        return SPECS["orthogonal"]
    if proto.scheme == "centralized":
        return SPECS["centralized"]
    if proto.scheme == "dwfl":
        if proto.topology != "complete":
            return SPECS["topology"]
        if proto.participation < 1.0:
            return SPECS["sampled"]
        return SPECS["complete"]
    raise ValueError(proto.scheme)


# ---------------------------------------------------------------------------
# persistent flat [W, d] parameter buffer — layout-aware spec
# ---------------------------------------------------------------------------


class FlatSpec:
    """Layout-aware flatten/unravel specification for the persistent flat
    parameter buffer.

    Built once from a template pytree (a real or ``jax.eval_shape`` tree —
    only shapes/dtypes are read), it owns the buffer CONTRACT every
    flat-buffer consumer shares: the leaf order/shapes/dtypes of the
    ravel, the number of leading batch axes (1: [W, d]; 2: the fleet's
    [R, W, d]), and — when a ``repro.shard.ShardLayout`` is attached — the
    model-axis shard geometry (physical width padded to
    ``layout.padded_width``, shard s owning global columns
    [s·shard_width, (s+1)·shard_width)). Padding columns are zeros and
    live PAST every leaf offset, so ``unravel``/``unravel_row`` read the
    same bytes whatever the layout — re-laying-out a buffer is a pure
    pad/slice (see checkpoint.restore_flat).

    ``flatten(X)``: ravel ONCE at init ([lead..., width] f32) — the
    flat-buffer training path then never re-concatenates per round.
    ``unravel(flat)``: full worker-stacked tree (original dtypes) — only
    at eval/checkpoint time. ``unravel_row(v)``: ONE worker's (un-stacked)
    tree — inside the per-worker grad vmap of the flat train step.

    ``max_chunk_cols`` (sharded specs) caps the column width of the
    gather-free grad pass's transfer chunks (``chunk_plan`` —
    repro.shard.layout.plan_chunks over this spec's leaf sizes): the
    sharded round then moves at most ~W·max_chunk_cols buffer elements
    per collective instead of a whole shard window. A pure data-movement
    knob — every budget realizes the bitwise-identical round.
    """

    def __init__(self, template: Tree, lead_axes: int = 1, layout=None,
                 max_chunk_cols: Optional[int] = None):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        self._treedef = treedef
        self._shapes = [tuple(l.shape) for l in leaves]
        self._dtypes = [l.dtype for l in leaves]
        self._sizes = [int(np.prod(s[lead_axes:])) for s in self._shapes]
        self.lead_axes = int(lead_axes)
        self.lead_shape = (tuple(self._shapes[0][:lead_axes])
                           if self._shapes else ())
        self.d = int(sum(self._sizes))
        if layout is not None and layout.d != self.d:
            raise ValueError(f"layout is for d={layout.d}, template ravels "
                             f"to d={self.d}")
        if max_chunk_cols is not None and layout is None:
            raise ValueError("max_chunk_cols is a sharded-buffer knob — "
                             "it requires a ShardLayout")
        self.layout = layout
        self.max_chunk_cols = (None if max_chunk_cols is None
                               else int(max_chunk_cols))
        self._chunk_plan = None

    @property
    def width(self) -> int:
        """Physical last-axis width of the buffer (d, or the layout's
        shard-padded width)."""
        return self.d if self.layout is None else self.layout.padded_width

    @property
    def n_shards(self) -> int:
        return 1 if self.layout is None else self.layout.n_shards

    def leaf_sizes(self) -> list:
        """Per-leaf flat sizes in ravel order (sum == d)."""
        return list(self._sizes)

    def leaf_offsets(self) -> list:
        """Global column offset of each leaf in the canonical [0, d)
        buffer (ravel order; the chunk plan's leaf boundaries)."""
        out, off = [], 0
        for n in self._sizes:
            out.append(off)
            off += n
        return out

    @property
    def chunk_plan(self):
        """The leaf x shard-window ChunkPlan of this spec (None for
        unsharded specs) — the schedule the gather-free sharded grad pass
        executes (repro.shard.round)."""
        if self.layout is None:
            return None
        if self._chunk_plan is None:
            from repro.shard.layout import plan_chunks
            self._chunk_plan = plan_chunks(self.layout, self._sizes,
                                           self.max_chunk_cols)
        return self._chunk_plan

    def flatten(self, X: Tree) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(X)
        flat = jnp.concatenate(
            [l.reshape(l.shape[:self.lead_axes] + (-1,)).astype(jnp.float32)
             for l in leaves], axis=-1)
        if self.width > self.d:
            pad = [(0, 0)] * self.lead_axes + [(0, self.width - self.d)]
            flat = jnp.pad(flat, pad)
        return flat

    def unpad(self, flat):
        """Physical buffer → the canonical (layout-independent) [..., d]
        view."""
        return flat[..., :self.d]

    def unravel(self, flat) -> Tree:
        """The worker-stacked tree of a buffer, as one compiled program (a
        slice and a reshape a leaf); a buffer whose columns are sharded is
        first laid out with whole rows on each device."""
        if getattr(self, "_unravel_jit", None) is None:
            self._unravel_jit = jax.jit(self._unravel)
        return self._unravel_jit(_whole_columns(flat))

    def _unravel(self, flat) -> Tree:
        out, off = [], 0
        lead = flat.shape[:-1]
        for s, dt, n in zip(self._shapes, self._dtypes, self._sizes):
            out.append(jax.lax.slice_in_dim(flat, off, off + n, axis=-1)
                       .reshape(lead + s[self.lead_axes:]).astype(dt))
            off += n
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def unravel_row(self, v) -> Tree:
        out, off = [], 0
        for s, dt, n in zip(self._shapes, self._dtypes, self._sizes):
            out.append(v[off:off + n].reshape(s[self.lead_axes:]).astype(dt))
            off += n
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def leaf_shapes(self) -> list:
        """Per-leaf shapes of ONE worker (the lead axes dropped), in ravel
        order."""
        return [s[self.lead_axes:] for s in self._shapes]

    def unravel_leaves(self, leaves) -> Tree:
        """ONE worker's tree from its float32 leaves in ravel order, each
        already in its own shape (``leaf_shapes``) — the leaf-addressed
        twin of ``unravel_row`` for a pass that never builds the row."""
        return jax.tree_util.tree_unflatten(
            self._treedef, [l.astype(dt) for l, dt in zip(leaves,
                                                          self._dtypes)])

    def layout_meta(self) -> dict:
        """JSON-able layout record for checkpoint manifests."""
        meta = {
            "d": self.d,
            "lead_axes": self.lead_axes,
            "lead_shape": list(self.lead_shape),
            "n_shards": self.n_shards,
            "width": self.width,
        }
        if self.layout is not None:
            meta["chunk_plan"] = self.chunk_plan.to_meta()
        return meta


def _whole_columns(flat):
    """A buffer whose columns are sharded over devices (the model axis),
    laid out anew with whole rows on each device (one row: on every
    device), so the leaves ``unravel`` slices out of it stay where their
    rows are; slicing the sharded columns would gather every leaf onto
    every device. Anything else passes through unchanged."""
    from jax.sharding import NamedSharding, PartitionSpec
    sh = getattr(flat, "sharding", None)
    if isinstance(flat, jax.core.Tracer) or not isinstance(sh, NamedSharding):
        return flat
    spec = tuple(sh.spec) + (None,) * (flat.ndim - len(sh.spec))
    if spec[-1] is None:
        return flat
    axes = spec[-1] if isinstance(spec[-1], tuple) else (spec[-1],)
    size = int(np.prod([sh.mesh.shape[a] for a in axes]))
    rows = (None,) * (flat.ndim - 2) + (spec[-1], None)
    ok = flat.ndim >= 2 and flat.shape[-2] % size == 0
    return jax.device_put(flat, NamedSharding(
        sh.mesh, PartitionSpec(*rows) if ok else PartitionSpec()))


def make_flat_spec(template: Tree, lead_axes: int = 1, layout=None,
                   n_shards: Optional[int] = None,
                   max_chunk_cols: Optional[int] = None) -> FlatSpec:
    """Build the FlatSpec for ``template``. Pass either a ready
    ``repro.shard.ShardLayout`` (``layout``) or just ``n_shards`` (> 1) to
    have the layout derived from the raveled width; the default is the
    legacy unsharded exact-d buffer. ``max_chunk_cols`` (sharded only)
    bounds the gather-free grad pass's per-collective chunk width."""
    if n_shards is not None and n_shards > 1:
        if layout is not None:
            raise ValueError("pass layout OR n_shards, not both")
        from repro.shard.layout import ShardLayout
        layout = ShardLayout(FlatSpec(template, lead_axes).d, n_shards)
    if layout is None:
        max_chunk_cols = None
    return FlatSpec(template, lead_axes, layout, max_chunk_cols)


def flatten_worker_tree(X: Tree, lead_axes: int = 1) -> jnp.ndarray:
    """Legacy wrapper: FlatSpec(X).flatten(X) with the unsharded exact-d
    layout (lead_axes=1: [W, d]; lead_axes=2: the fleet's [R, W, d])."""
    return FlatSpec(X, lead_axes).flatten(X)


def worker_unravelers(template: Tree, lead_axes: int = 1):
    """Legacy wrapper: the (unravel, unravel_row) pair of
    FlatSpec(template, lead_axes)."""
    spec = FlatSpec(template, lead_axes)
    return spec.unravel, spec.unravel_row
