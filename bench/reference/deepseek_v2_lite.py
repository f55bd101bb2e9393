"""Plain reference of DWFL training on one chip's share of DeepSeek-V2-Lite.

Written from DeepSeek-V2 (arXiv:2405.04434: latent attention, YaRN rope,
the DeepSeekMoE layer and its sequence-wise balance loss), from the DWFL
paper (arXiv:2109.09142: Alg. 1, Eqt. 3-4, Thm 4.1) and from the numbers in
the configuration file alone: the published config's keys at the file's
top level (as run: depth, experts held and vocabulary cut), the router's
published expert count under ``reduced``, the rest under ``model``. It
imports nothing of the program under test; the DWFL parts it shares with
the MLP cell's reference (channel, ledger, rounding, state readings) come
from ``dwfl_mlp``.

What it reproduces exactly is what the seed fixes: the bigram token stream
and each worker's slice of it, the window each worker draws in each round,
the initial weights (shared by every worker, drawn by the same law as the
program's), the channel and its sigma, and the privacy ledger. The noise it
draws from a stream of its own, as ``dwfl_mlp`` does: with noiseless
receivers the DP noise cancels from the mean row.

Every matrix product rounds both operands to the configuration's
``matmul`` type and accumulates in float32 (``product``), in the forward
pass and in both products of the backward pass: the projections, the
attention scores and their softmax-weighted sum, the router, the experts
and the output head. The routed experts are computed densely over the
experts held, each weighted by its gate where the token chose it and by
zero elsewhere: the same sum as the program's grouped products over the
chosen pairs. Attention runs over query blocks of 1,024, which is exact.

Memory: each worker's row (2.14 GB at the cell's size) lives on a chip of
its own where there are as many chips as workers; its gradient is taken
there, one worker at a time, each layer recomputed in the backward pass;
the mix and the state readings run over column chunks gathered from the
workers' chips. The faults are values of the compiled programs, not
constants, so one compile serves the check and every fault (the control,
one operand type lower, is another program).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dwfl_mlp import (  # noqa: F401  (the harness reads these)
    CONTROL, HIGHEST, NOISE_STREAM, keyed, ledger_eps, rdp_to_eps, rounded,
    static_channel)

FAULTS = (
    "unchanged",     # the round returns the state it was given
    "half_batch",    # each worker's loss and gradient over half its tokens
    "no_exchange",   # the mix left out: the local step alone
    "no_step",       # the gradient term left out of the update
    "no_clip",       # the gradient applied unclipped
    "no_dp",         # the DP noise left out
)
CHUNK = 1 << 24          # columns of a row moved between chips at once
Q_BLOCK = 1024           # query block of the attention


class Dims(NamedTuple):
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    ff: int
    expert_ff: int
    router: int          # the router's outputs: every routed expert
    held: int
    first: int
    topk: int
    shared: int
    dense: int
    moe: int
    vocab: int
    eps: float
    theta: float
    rope_factor: float
    rope_orig: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all: float
    norm_topk: bool
    routed_scale: float
    alpha: float


def dims(cfg) -> Dims:
    ys = cfg["rope_scaling"]
    return Dims(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], expert_ff=cfg["moe_intermediate_size"],
        router=cfg["reduced"]["n_routed_experts"],
        held=cfg["n_routed_experts"], first=cfg["model"]["first_expert"],
        topk=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        dense=cfg["first_k_dense_replace"],
        moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        vocab=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]), rope_factor=float(ys["factor"]),
        rope_orig=ys["original_max_position_embeddings"],
        beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
        mscale=float(ys["mscale"]), mscale_all=float(ys["mscale_all_dim"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        alpha=float(cfg["model"]["aux_alpha"]))


# ----------------------------------------------------------------- data

def token_stream(n_tokens, vocab, seed):
    """The sparse random bigram chain: 32 successors per id."""
    rng = np.random.default_rng(seed)
    branch = min(32, vocab)
    nxt = rng.integers(0, vocab, size=(vocab, branch))
    toks = np.empty(n_tokens, np.int32)
    t = rng.integers(0, vocab)
    choices = rng.integers(0, branch, size=n_tokens)
    for i in range(n_tokens):
        toks[i] = t
        t = nxt[t, choices[i]]
    return toks


def draw_windows(key, tokens, workers, batch, seq):
    """Each worker's batch: ``batch`` windows of ``seq`` tokens at uniform
    starts in its own equal slice of the stream ([W, batch, seq])."""
    span = tokens.shape[0] // workers
    s = jax.random.randint(key, (workers, batch), 0, span - seq - 1)
    pos = (jnp.arange(workers)[:, None, None] * span + s[:, :, None]
           + jnp.arange(seq)[None, None, :])
    return tokens[pos]


@jax.jit
def _leaf_stats(x, x0):
    x = x.astype(jnp.float32).reshape(x.shape[0], -1)
    mean = jnp.mean(x, axis=0)
    return (jnp.sqrt(jnp.sum((mean - x0.reshape(-1)) ** 2)),
            jnp.sum((x - mean[None]) ** 2, axis=1))


def state_stats(leaves, leaves0):
    """What the comparison reads of the program's state after the first
    chunk, given its leaves ([N, ...] each) and the shared initial row's:
    the norm of the mean row's change in each leaf ([L]) and each worker's
    distance from the mean row ([N]); one leaf at a time, so a leaf's mean
    is the largest temporary at the cell's size."""
    norms, dev2 = [], 0.0
    for x, x0 in zip(leaves, leaves0):
        n, d2 = _leaf_stats(x, x0)
        norms.append(n)
        dev2 = dev2 + d2
    return jnp.stack(norms), jnp.sqrt(dev2)


# ---------------------------------------------------------------- model

def _dense(key, fan_in, fan_out):
    scale = 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, (fan_in, fan_out)) * scale


def init_params(key, m: Dims):
    """The shared initial weights: N(0, 1/fan_in) products, N(0, 0.02^2)
    embedding, unit norm scales, expert e drawn from its own fold of the
    layer's key; keys split as the layers nest."""
    ke, kd, km, _ = jax.random.split(key, 4)
    ones = lambda n: jnp.ones((n,), jnp.float32)

    def attn(k):
        kq, ka, kb, ko = jax.random.split(k, 4)
        return {"wq": _dense(kq, m.d, m.heads * (m.nope + m.rope)),
                "wkv_a": _dense(ka, m.d, m.kv_rank + m.rope),
                "kv_norm": ones(m.kv_rank),
                "wkv_b": _dense(kb, m.kv_rank, m.heads * (m.nope + m.v)),
                "wo": _dense(ko, m.heads * m.v, m.d)}

    def swiglu(k, ff):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": _dense(k1, m.d, ff), "w_up": _dense(k2, m.d, ff),
                "w_down": _dense(k3, ff, m.d)}

    def dense_block(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": {"scale": ones(m.d)}, "attn": attn(k1),
                "norm2": {"scale": ones(m.d)}, "mlp": swiglu(k2, m.ff)}

    def moe_block(k):
        k1, k2 = jax.random.split(k)
        kr, kg, ku, kdn, ks = jax.random.split(k2, 5)
        held = jnp.arange(m.first, m.first + m.held)

        def experts(kk, fan_in, fan_out):
            return jax.vmap(lambda e: _dense(jax.random.fold_in(kk, e),
                                             fan_in, fan_out))(held)
        moe = {"router": _dense(kr, m.d, m.router),
               "w_gate": experts(kg, m.d, m.expert_ff),
               "w_up": experts(ku, m.d, m.expert_ff),
               "w_down": experts(kdn, m.expert_ff, m.d)}
        if m.shared:
            moe["shared"] = swiglu(ks, m.expert_ff * m.shared)
        return {"norm1": {"scale": ones(m.d)}, "attn": attn(k1),
                "norm2": {"scale": ones(m.d)}, "moe": moe}

    p = {"embed": {"tok": jax.random.normal(ke, (m.vocab, m.d)) * 0.02,
                   "unembed": _dense(jax.random.fold_in(ke, 1), m.d,
                                     m.vocab)},
         "moe_blocks": jax.vmap(moe_block)(jax.random.split(km, m.moe)),
         "final_norm": {"scale": ones(m.d)}}
    if m.dense:
        p["dense_blocks"] = jax.vmap(dense_block)(
            jax.random.split(kd, m.dense))
    return p


# Operands rounded to bfloat16 multiply exactly in float32, so one
# bfloat16 pass with float32 accumulation (the TPU's DEFAULT on float32
# operands; exact float32 on the CPU) gives the products HIGHEST gives, at
# a sixth of its passes. Any other type keeps HIGHEST.
PASSES = {"bfloat16": jax.lax.Precision.DEFAULT}


def _einsum(spec, kind, a, b):
    return jnp.einsum(spec, a, b, precision=PASSES.get(kind, HIGHEST))


@partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def product(spec, a, b, kind):
    """einsum of a and b with both operands rounded to ``kind``, float32
    accumulation; the backward pass's two products round theirs alike."""
    return _einsum(spec, kind, rounded(a, kind), rounded(b, kind))


def _product_fwd(spec, a, b, kind):
    return product(spec, a, b, kind), (a, b)


def _product_bwd(spec, kind, res, g):
    a, b = res
    _, vjp = jax.vjp(partial(_einsum, spec, kind), rounded(a, kind),
                     rounded(b, kind))
    return vjp(rounded(g, kind))


product.defvjp(_product_fwd, _product_bwd)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_g(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(m: Dims):
    """YaRN: inv_freq = f/factor (1 - k) + f k, k = 1 - ramp from
    floor(c(beta_fast)) to ceil(c(beta_slow)), c(r) = D ln(L0 / 2 pi r) /
    (2 ln theta)."""
    D = m.rope
    f = 1.0 / m.theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    if m.rope_factor <= 1:
        return f

    def c(r):
        return D * math.log(m.rope_orig / (r * 2 * math.pi)) / (
            2 * math.log(m.theta))
    lo, hi = max(math.floor(c(m.beta_fast)), 0), min(math.ceil(
        c(m.beta_slow)), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / m.rope_factor * (1.0 - keep) + f * keep


def rotate(x, pos, m: Dims):
    """Rotary position of x [B, S, ..., D] (split-half channel order), cos
    and sin scaled by g(factor, mscale) / g(factor, mscale_all_dim)."""
    scale = (yarn_g(m.rope_factor, m.mscale)
             / yarn_g(m.rope_factor, m.mscale_all))
    ang = pos[:, None].astype(jnp.float32) * rope_freqs(m)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3)
                      + (ang.shape[1],))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(m: Dims):
    s = 1.0 / math.sqrt(m.nope + m.rope)
    if m.rope_factor > 1 and m.mscale_all:
        s *= yarn_g(m.rope_factor, m.mscale_all) ** 2
    return s


def attention(p, x, m: Dims, kind):
    B, S, _ = x.shape
    H = m.heads
    q = product("bsd,de->bse", x, p["wq"], kind).reshape(
        B, S, H, m.nope + m.rope)
    kv_a = product("bsd,de->bse", x, p["wkv_a"], kind)
    c = rms(kv_a[..., :m.kv_rank], p["kv_norm"], m.eps)
    kv = product("bsr,re->bse", c, p["wkv_b"], kind).reshape(
        B, S, H, m.nope + m.v)
    pos = jnp.arange(S)
    q_nope, q_rope = q[..., :m.nope], rotate(q[..., m.nope:], pos, m)
    k_nope, v = kv[..., :m.nope], kv[..., m.nope:]
    k_rope = rotate(kv_a[..., m.kv_rank:], pos, m)           # one, shared
    Q = min(Q_BLOCK, S)

    def block(_, a):
        """Queries [a, a + Q) against every key, the later ones masked."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, a, Q, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, a, Q, axis=1)
        s = (product("bqhd,bkhd->bhqk", qn, k_nope, kind)
             + product("bqhd,bkd->bhqk", qr, k_rope, kind)) * score_scale(m)
        causal = pos[None, :] <= (a + jnp.arange(Q))[:, None]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return None, product("bhqk,bkhd->bqhd", w, v, kind)
    _, o = jax.lax.scan(block, None, jnp.arange(0, S, Q))   # [S/Q, B, Q..]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * m.v)
    return product("bse,ed->bsd", o, p["wo"], kind)


def swiglu(p, x, kind):
    h = jax.nn.silu(product("bsd,df->bsf", x, p["w_gate"], kind))
    h = h * product("bsd,df->bsf", x, p["w_up"], kind)
    return product("bsf,fd->bsd", h, p["w_down"], kind)


def moe(p, x, m: Dims, kind):
    """Held experts' part of the routed output (every expert held here on
    every token, weighted by its gate where the token chose it), the shared
    experts, and the sequence-wise balance loss."""
    probs = jax.nn.softmax(product("bsd,de->bse", x, p["router"], kind), -1)
    gates, idx = jax.lax.top_k(probs, m.topk)
    if m.norm_topk:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * m.routed_scale
    weight = jnp.sum(jax.nn.one_hot(idx - m.first, m.held)
                     * gates[..., None], axis=2)            # [B, S, held]
    h = jax.nn.silu(product("bsd,edf->bsef", x, p["w_gate"], kind))
    h = h * product("bsd,edf->bsef", x, p["w_up"], kind)
    y = product("bsef,efd->bsed", h, p["w_down"], kind)
    y = jnp.sum(y * weight[..., None], axis=2)
    if m.shared:
        y = y + swiglu(p["shared"], x, kind)
    S = x.shape[1]
    f = jnp.sum(jax.nn.one_hot(idx, m.router), axis=(1, 2)) * (
        m.router / (S * m.topk))
    return y, jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), -1))


def loss_fn(p, tokens, weight, m: Dims, kind):
    """Next-token cross-entropy over the sliced vocabulary, each target
    weighted (all 1, or the half-batch fault's first half), plus alpha times
    the balance loss summed over the MoE layers."""
    @jax.checkpoint
    def dense_layer(x, q):
        x = x + attention(q["attn"], rms(x, q["norm1"]["scale"], m.eps), m,
                          kind)
        return x + swiglu(q["mlp"], rms(x, q["norm2"]["scale"], m.eps),
                          kind), None

    @jax.checkpoint
    def moe_layer(x, q):
        x = x + attention(q["attn"], rms(x, q["norm1"]["scale"], m.eps), m,
                          kind)
        y, aux = moe(q["moe"], rms(x, q["norm2"]["scale"], m.eps), m, kind)
        return x + y, aux

    x = p["embed"]["tok"][tokens]
    if m.dense:
        x, _ = jax.lax.scan(dense_layer, x, p["dense_blocks"])
    x, aux = jax.lax.scan(moe_layer, x, p["moe_blocks"])
    aux = jnp.sum(aux)
    x = rms(x, p["final_norm"]["scale"], m.eps)
    logits = product("bsd,dv->bsv", x, p["embed"]["unembed"], kind)[:, :-1]
    target = tokens[:, 1:]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, target[..., None], -1)[..., 0])
    w = jnp.broadcast_to(weight, nll.shape)
    return jnp.sum(nll * w) / jnp.sum(w) + m.alpha * aux


# ------------------------------------------------------------ flat rows

class Layout(NamedTuple):
    treedef: object
    shapes: tuple
    d: int
    width: int           # d padded to whole chunks

    def unflat(self, row):
        out, o = [], 0
        for s in self.shapes:
            n = int(np.prod(s))
            out.append(row[o:o + n].reshape(s))
            o += n
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def leaves(self, row):
        return jax.tree_util.tree_leaves(self.unflat(row))


def layout_of(m: Dims) -> Layout:
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), m))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(x.shape) for x in leaves)
    d = sum(int(np.prod(s)) for s in shapes)
    chunk = min(CHUNK, -(-d // 128) * 128)
    return Layout(treedef, shapes, d, -(-d // chunk) * chunk)


def chunk_of(lay: Layout) -> int:
    return min(CHUNK, lay.width)


def initial_row(key, *, m, lay):
    """The shared initial weights as one row. Drawn and laid in a row by
    two programs: fused into the concatenation, the draws take minutes to
    compile at the cell's size."""
    return _as_row(jax.jit(init_params, static_argnames="m")(key, m=m),
                   lay=lay)


@partial(jax.jit, static_argnames=("lay",))
def _as_row(tree, *, lay):
    row = jnp.concatenate([x.reshape(-1)
                           for x in jax.tree_util.tree_leaves(tree)])
    return jnp.pad(row, (0, lay.width - lay.d))


@partial(jax.jit, static_argnames=("m", "lay", "kind", "clip", "gamma"),
         donate_argnums=(0,))
def local_step(row, tokens, weight, clip_on, step_on, *, m, lay, kind,
               clip, gamma):
    """One worker's loss, pre-clip gradient norm, clipped gradient and
    local step (its row less gamma times the gradient; the faults switch
    the clip or the step off)."""
    loss, g = jax.value_and_grad(
        lambda r: loss_fn(lay.unflat(r), tokens, weight, m, kind))(row)
    gn = jnp.sqrt(jnp.sum(g * g))
    scale = jnp.where(clip_on, jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)),
                      1.0)
    g = g * scale
    return row - step_on * gamma * g, loss, gn, g


@partial(jax.jit, static_argnames=("lay",))
def dp_noise(key, amp, *, lay):
    """The DP noise a worker adds to its row: amp * N(0, 1) on its real
    columns."""
    z = jax.random.normal(key, (lay.width,), jnp.float32)
    return jnp.where(jnp.arange(lay.width) < lay.d, amp * z, 0.0)


@partial(jax.jit, static_argnames=("kind", "size"))
def sent(x, nf, c, *, kind, size):
    """Chunk c of what a worker transmits: its row plus its DP noise,
    rounded to the mix's type."""
    return rounded(jax.lax.dynamic_slice(x + nf, (c * size,), (size,)), kind)


@partial(jax.jit, static_argnames=("kind",))
def mixed(W, ys, *, kind):
    """Each receiver's superposition of the transmitted chunks [N, C]."""
    return jnp.dot(rounded(W, kind), ys, precision=HIGHEST)


@partial(jax.jit, static_argnames=("size",))
def received(x, nf, mix_i, c, eta, k_m, m_amp, *, size):
    """Eqt. 8 on a chunk: the superposition and the receiver's channel
    noise (m_amp * N(0, 1), drawn chunk by chunk), less its own row and the
    DP noise it injected itself."""
    xs = jax.lax.dynamic_slice(x, (c * size,), (size,))
    ns = jax.lax.dynamic_slice(nf, (c * size,), (size,))
    ms = m_amp * jax.random.normal(jax.random.fold_in(k_m, c), (size,))
    return xs + eta * (mix_i + ms - xs - ns)


@partial(jax.jit, static_argnames=("size",))
def piece(row, c, *, size):
    return jax.lax.dynamic_slice(row, (c * size,), (size,))


@jax.jit
def mean_and_dev(ys):
    """[N, C] chunk of the rows -> its mean row and each row's squared
    distance from it."""
    mean = jnp.mean(ys, axis=0)
    return mean, jnp.sum((ys - mean[None]) ** 2, axis=1)


@partial(jax.jit, static_argnames=("lay",))
def leaf_norms(row, row0, *, lay):
    return jnp.stack([jnp.sqrt(jnp.sum((a - b) ** 2)) for a, b in
                      zip(lay.leaves(row), lay.leaves(row0))])


def _gather(rows, c, size, dev):
    """Chunk c of every row, stacked on ``dev``."""
    return jnp.stack([jax.device_put(piece(r, c, size=size), dev)
                      for r in rows])


def _mean_row(rows, lay, devs):
    """The rows' mean, assembled on the first chip, and each row's squared
    distance from it, chunk by chunk."""
    size = chunk_of(lay)
    parts, dev2 = [], 0.0
    for c in range(lay.width // size):
        mean, sq = mean_and_dev(_gather(rows, c, size, devs[c % len(devs)]))
        parts.append(jax.device_put(mean, devs[0]))
        dev2 = dev2 + np.asarray(sq, np.float64)
    return jnp.concatenate(parts), dev2


# ---------------------------------------------------------------- rounds

def run(cfg, seed, rounds, *, control=False, fault=None,
        noise_stream=NOISE_STREAM):
    """Follow the first ``rounds`` rounds from ``seed``. Returns each
    round's mean loss and mean pre-clip gradient norm, the state statistics
    after the last round, the norm per leaf of round 0's mean clipped
    gradient, and the composed epsilon of the trajectory."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    with jax.default_matmul_precision("highest"):
        return _run(cfg, seed, rounds, control, fault, noise_stream)


def _run(cfg, seed, rounds, control, fault, noise_stream):
    pr, model = cfg["protocol"], cfg["model"]
    m = dims(cfg)
    lay = layout_of(m)
    size = chunk_of(lay)
    N, B, S = pr["workers"], pr["batch_size"], model["seq_len"]
    kind = CONTROL[model["matmul"]] if control else model["matmul"]
    mix_kind = CONTROL["float32"] if control else "float32"
    devs = jax.devices()
    devs = [devs[i % len(devs)] for i in range(N)]
    tokens = jnp.asarray(token_stream(N * 200_000, m.vocab, seed))
    weight = jnp.ones((S - 1,), jnp.float32)
    if fault == "half_batch":
        weight = weight.at[(S - 1) // 2:].set(0.0)
    clip_on = jnp.float32(fault != "no_clip")
    step_on = jnp.float32(fault != "no_step")
    plan, rho, _ = static_channel(cfg, seed)
    key = jax.random.PRNGKey(seed)
    p0 = initial_row(key, m=m, lay=lay)
    rows = [jax.device_put(p0, dv, may_alias=False) for dv in devs]
    del p0
    noise_key = jax.random.fold_in(jax.random.PRNGKey(seed), noise_stream)
    W = jnp.asarray(plan["W"], jnp.float32)
    losses, gnorms, rho_sum, g0 = [], [], 0.0, None
    for t in range(rounds):
        key, sk = jax.random.split(key)
        k_data, _ = jax.random.split(sk)
        batch = draw_windows(k_data, tokens, N, B, S)
        xs, ls, gs, grads = [], [], [], []
        for i in range(N):
            x, loss, gn, g = local_step(
                rows[i], jax.device_put(batch[i], devs[i]), weight, clip_on,
                step_on, m=m, lay=lay, kind=kind, clip=pr["clip"],
                gamma=pr["gamma"])
            xs.append(x)
            ls.append(loss)
            gs.append(gn)
            grads.append(g if t == 0 else None)
            del g
        rows = None
        if t == 0:
            mean_g, _ = _mean_row(grads, lay, devs)
            g0 = np.asarray(leaf_norms(mean_g, jnp.zeros_like(mean_g),
                                       lay=lay), np.float64)
            del mean_g
        grads = None
        losses.append(float(np.mean([float(a) for a in ls])))
        gnorms.append(float(np.mean([float(a) for a in gs])))
        if control:
            rho_sum = float(jnp.asarray(rho_sum, jnp.bfloat16)
                            + jnp.asarray(rho, jnp.bfloat16))
        else:
            rho_sum += rho
        if fault in ("unchanged", "no_exchange"):
            if fault == "no_exchange":
                rows = xs
            else:       # the state it was given: the initial row
                p0 = initial_row(jax.random.PRNGKey(seed), m=m, lay=lay)
                rows = [jax.device_put(p0, dv, may_alias=False)
                        for dv in devs]
                del p0
            continue
        rk = jax.random.fold_in(noise_key, t)
        nfs, kms = [], []
        for i in range(N):
            k_n, k_m = jax.random.split(jax.random.fold_in(rk, i))
            amp = 0.0 if fault == "no_dp" else plan["amp"][i] / plan["c"]
            nfs.append(dp_noise(jax.device_put(k_n, devs[i]),
                                jnp.float32(amp), lay=lay))
            kms.append(jax.device_put(k_m, devs[i]))
        new = [[] for _ in range(N)]
        for c in range(lay.width // size):
            home = devs[c % N]
            ys = jnp.stack([jax.device_put(
                sent(xs[j], nfs[j], c, kind=mix_kind, size=size), home)
                for j in range(N)])
            mix = mixed(jax.device_put(W, home), ys, kind=mix_kind)
            for i in range(N):
                new[i].append(received(
                    xs[i], nfs[i], jax.device_put(mix[i], devs[i]), c,
                    pr["eta"], kms[i],
                    jnp.float32(plan["m_scale"][i] * pr["sigma_m"]),
                    size=size))
            del ys, mix
        del xs, nfs
        rows = [jnp.concatenate(parts) for parts in new]
        del new
    mean, dev2 = _mean_row(rows, lay, devs)
    del rows
    p0 = initial_row(jax.random.PRNGKey(seed), m=m, lay=lay)
    leaf = np.asarray(leaf_norms(mean, jax.device_put(p0, devs[0]), lay=lay),
                      np.float64)
    shapes = [s for s in lay.shapes]
    return {"loss": losses, "grad_norm": gnorms,
            "change_leaf": keyed(shapes, leaf),
            "row_dev": np.sqrt(dev2),
            "grad0_leaf": keyed(shapes, g0),
            "eps": rdp_to_eps(rho_sum, rounds, pr["delta"]),
            "rounds": rounds}
