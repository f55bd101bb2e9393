"""Plain reference of DWFL training on the dwfl-paper MLP.

Written from the paper (arXiv:2109.09142: Alg. 1, Eqt. 3-4, Thm 4.1) and
from the numbers in the configuration file alone. It imports nothing of the
program under test and takes nothing it made: the data, the initial
weights, the channel and the noise are all drawn here from the run's seed.

What it reproduces exactly is what the seed fixes: the synthetic data set
and its Dirichlet split, the initial weights (shared by every worker), the
batch each worker draws in each round, the channel and its sigma, and the
privacy ledger. What it cannot reproduce is the program's noise: the chip
draws it with its own generator. The reference draws noise of the same law
from another stream. With noiseless receivers (sigma_m = 0) each receiver
subtracts the DP noise it injected, so the DP noise cancels from the mean
of the workers' rows: the mean row moves by the clipped gradient steps
alone, which is what makes the local step visible through the noise.

Matrix products follow the configuration's ``matmul`` entry: each operand
rounded to that type, products accumulated in float32, in the forward pass
and in both products of the backward pass. The control is one step lower
(``CONTROL``): per-tensor scaled float8 operands for the gradient pass, the
mix in bfloat16 and the privacy ledger in bfloat16. ``fault`` plants one of
the faults the comparison has to catch (``FAULTS``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the ledger's Renyi orders and its delta' term (the trajectory quote, at
# total delta = T * delta + DELTA_PRIME)
ORDERS = (1.25, 1.5, 1.75, 2.0, 2.5, 3.0, 3.5, 4.0, 5.0, 6.0, 8.0, 10.0,
          12.0, 16.0, 20.0, 24.0, 32.0, 48.0, 64.0, 96.0, 128.0, 192.0,
          256.0, 384.0, 512.0)
DELTA_PRIME = 1e-6
H_FLOOR = 0.05          # fading magnitude floor
POWER_FLOOR = 0.05      # share of power kept for noise before alignment
NOISE_STREAM = 0x7E5    # fold-in tag of the reference's own noise stream
FP8_MAX = 448.0         # largest float8_e4m3fn
# the step below each stated operand type: the control
CONTROL = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
FAULTS = (
    "unchanged",     # the round returns the state it was given
    "half_batch",    # each worker's loss and gradient over half its batch
    "no_exchange",   # the mix left out: the local step alone
    "no_step",       # the gradient term left out of the update
    "no_clip",       # the gradient applied unclipped
    "no_dp",         # the DP noise left out
)


# ----------------------------------------------------------------- data

def dataset(n, input_dim, classes, seed, label_noise=0.05, rank=64):
    """The synthetic CIFAR-shaped task: low-rank Gaussian features and a
    linear-softmax teacher with label noise (x [n, D] f32, y [n] i32)."""
    rng = np.random.default_rng(seed)
    mix = rng.normal(size=(rank, input_dim)).astype(np.float32)
    z = rng.normal(size=(n, rank)).astype(np.float32)
    x = (z @ mix) / np.sqrt(rank)
    teacher = rng.normal(size=(rank, classes)).astype(np.float32)
    logits = z @ teacher + 0.5 * rng.normal(size=(n, classes)).astype(
        np.float32)
    y = logits.argmax(-1).astype(np.int32)
    flip = rng.random(n) < label_noise
    y[flip] = rng.integers(0, classes, flip.sum(), dtype=np.int32)
    return x, y


def partition(y, n_workers, alpha, seed):
    """Dirichlet(alpha) label split into equal shares of n // N samples."""
    rng = np.random.default_rng(seed)
    classes = np.unique(y)
    per_worker = len(y) // n_workers
    props = rng.dirichlet([alpha] * len(classes), size=n_workers)
    by_class = {c: rng.permutation(np.where(y == c)[0]).tolist()
                for c in classes}
    out = []
    for w in range(n_workers):
        want = (props[w] / props[w].sum() * per_worker).astype(int)
        take = []
        for ci, c in enumerate(classes):
            take.extend(by_class[c][:want[ci]])
            by_class[c] = by_class[c][want[ci]:]
        pool = [i for c in classes for i in by_class[c]]
        rng.shuffle(pool)
        while len(take) < per_worker and pool:
            take.append(pool.pop())
        taken = set(take)
        for c in classes:
            by_class[c] = [i for i in by_class[c] if i not in taken]
        out.append(np.array(take[:per_worker], np.int64))
    return out


def pools(parts):
    """[W, m] wrap-padded index pools and their true sizes."""
    m = max(len(p) for p in parts)
    pool = np.stack([np.resize(np.asarray(p, np.int32), m) for p in parts])
    return pool, np.array([len(p) for p in parts], np.int32)


def draw_batch(key, x, y, pool, size, batch):
    """Each worker's batch: uniform draws with replacement from its pool."""
    u = jax.random.uniform(key, (pool.shape[0], batch))
    j = jnp.minimum((u * size[:, None].astype(jnp.float32)).astype(jnp.int32),
                    size[:, None] - 1)
    g = jnp.take_along_axis(pool, j, axis=1)
    return x[g], y[g]


# ---------------------------------------------------------------- model

def layer_dims(model):
    return ([model["input_dim"]] + [model["hidden"]] * model["hidden_layers"]
            + [model["classes"]])


def init_flat(key, dims):
    """Shared initial weights, N(0, 1/fan_in), zero biases, as one row."""
    keys = jax.random.split(key, len(dims) - 1)
    parts = []
    for k, (i, o) in zip(keys, zip(dims[:-1], dims[1:])):
        parts += [(jax.random.normal(k, (i, o)) * (1.0 / math.sqrt(i))
                   ).reshape(-1), jnp.zeros((o,), jnp.float32)]
    return jnp.concatenate(parts)


def unflat(v, dims):
    out, o = [], 0
    for i, n in zip(dims[:-1], dims[1:]):
        out.append((v[o:o + i * n].reshape(i, n), v[o + i * n:o + i * n + n]))
        o += i * n + n
    return out


def rounded(a, kind):
    """``a`` with its values rounded to ``kind`` (float8: scaled per tensor
    so its largest magnitude maps to the type's largest), as float32."""
    if kind == "float32":
        return a
    if kind == "float8_e4m3fn":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
        return (a / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    return a.astype(jnp.dtype(kind)).astype(jnp.float32)


def _dot(a, b, kind):
    # products of rounded operands are exact in float32; HIGHEST keeps the
    # float32 accumulation on every backend
    return jnp.dot(rounded(a, kind), rounded(b, kind), precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul(a, b, kind):
    """a @ b with both operands rounded to ``kind``; the backward pass's
    two products round theirs the same way."""
    return _dot(a, b, kind)


def _matmul_fwd(a, b, kind):
    return _dot(a, b, kind), (a, b)


def _matmul_bwd(kind, res, g):
    a, b = res
    return _dot(g, b.T, kind), _dot(a.T, g, kind)


matmul.defvjp(_matmul_fwd, _matmul_bwd)


def loss_fn(v, xb, yb, dims, kind):
    h = xb
    layers = unflat(v, dims)
    for li, (w, b) in enumerate(layers):
        h = matmul(h, w, kind) + b
        if li < len(layers) - 1:
            h = jax.nn.relu(h)
    lse = jax.nn.logsumexp(h, axis=-1)
    picked = jnp.take_along_axis(h, yb[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


# -------------------------------------------------------------- channel

def align(h, P):
    """Power alignment (Eqt. 3-4) on a budget derated by the noise floor;
    all surplus power carries DP noise. Returns (c, noise amplitude s)."""
    eff = h * h * P
    alpha = (1.0 - POWER_FLOOR) * eff.min() / eff
    c = (1.0 - POWER_FLOOR) * eff.min()
    return c ** 0.5, h * ((1.0 - alpha) * P) ** 0.5


def noise_multiplier(eps, delta):
    if not 0 < eps <= 1:
        raise ValueError("the classic Gaussian constant needs 0 < eps <= 1")
    return math.sqrt(2.0 * math.log(1.25 / delta)) / eps


def static_channel(cfg, seed):
    """The one-shot Rayleigh channel on the complete graph, sigma calibrated
    so the worst receiver's per-round budget is the target (Thm 4.1).
    Returns float64 numpy values shared by every round, and the round's
    RDP rate."""
    pr = cfg["protocol"]
    N = pr["workers"]
    rng = np.random.default_rng(seed)
    h = np.maximum(rng.rayleigh(scale=1.0 / np.sqrt(2.0), size=N), H_FLOOR)
    P = 10.0 ** ((pr["p_dbm"] - 30.0) / 10.0)
    c, s = align(h, np.full(N, P))
    mask_sum = (s ** 2).sum() - s ** 2
    need = ((2.0 * pr["gamma"] * pr["clip"] * c
             * noise_multiplier(pr["epsilon"], pr["delta"])) ** 2
            - pr["sigma_m"] ** 2)
    sigma = max(math.sqrt(need / mask_sum.min()) if need > 0 else 0.0, 1e-12)
    agg2 = mask_sum * sigma ** 2 + pr["sigma_m"] ** 2
    rho = float(np.max((2.0 * pr["gamma"] * pr["clip"] * c) ** 2
                       / (2.0 * agg2)))
    plan = {"c": c, "amp": s * sigma,
            "W": (np.ones((N, N)) - np.eye(N)) / (N - 1),
            "m_scale": np.full(N, 1.0 / (c * (N - 1)))}
    return plan, rho, sigma


def rdp_to_eps(rho_sum, rounds, delta):
    """(eps, delta)-DP of T rounds from the summed RDP rate (order alpha
    costs alpha * rho_sum), quoted at total delta T * delta + delta'."""
    al = np.asarray(ORDERS)
    d = rounds * delta + DELTA_PRIME
    conv = al * rho_sum + np.log1p(-1.0 / al) - (np.log(d) + np.log(al)) / (
        al - 1.0)
    return max(float(conv.min()), 0.0) if rho_sum > 0 else 0.0


def ledger_eps(moments, delta):
    """The program's in-scan ledger [sum eps, sum eps^2, ..., T | per-order
    RDP sums] turned into its trajectory quote, by the conversion above."""
    m = np.asarray(moments, np.float64)
    al = np.asarray(ORDERS)
    rounds = m[3]
    d = rounds * delta + DELTA_PRIME
    conv = m[4:] + np.log1p(-1.0 / al) - (np.log(d) + np.log(al)) / (al - 1.0)
    return (max(float(conv.min()), 0.0) if m[4:].sum() > 0 else 0.0), rounds


# ---------------------------------------------------------------- rounds

@partial(jax.jit, static_argnames=("dims", "batch", "gamma", "eta", "clip",
                                   "sigma_m", "kind", "mix_kind", "fault"))
def _round(p, plan, k_data, k_noise, data, *, dims, batch, gamma, eta, clip,
           sigma_m, kind, mix_kind, fault):
    x_all, y_all, pool, size = data
    xb, yb = draw_batch(k_data, x_all, y_all, pool, size, batch)
    if fault == "half_batch":
        xb, yb = xb[:, :batch // 2], yb[:, :batch // 2]
    losses, g = jax.vmap(jax.value_and_grad(
        lambda v, a, b: loss_fn(v, a, b, dims, kind)))(p, xb, yb)
    gn = jnp.sqrt(jnp.sum(g * g, axis=1))
    if fault != "no_clip":
        g = g * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))[:, None]
    x = p if fault == "no_step" else p - gamma * g
    if fault == "unchanged":
        return p, jnp.mean(losses), jnp.mean(gn), g
    if fault == "no_exchange":
        return x, jnp.mean(losses), jnp.mean(gn), g
    k_n, k_m = jax.random.split(k_noise)
    nf = (plan["amp"] / plan["c"])[:, None] * jax.random.normal(
        k_n, x.shape, jnp.float32)
    if fault == "no_dp":
        nf = jnp.zeros_like(nf)
    m = sigma_m * jax.random.normal(k_m, x.shape, jnp.float32)
    # Eqt. 8 on the complete graph: the received superposition of the
    # others' noised rows, the receiver's channel noise, minus its own row
    # and the DP noise it injected itself
    mixed = jnp.dot(rounded(plan["W"], mix_kind),
                    rounded(x + nf, mix_kind), precision=HIGHEST)
    upd = mixed + plan["m_scale"][:, None] * m - x - nf
    return x + eta * upd, jnp.mean(losses), jnp.mean(gn), g


def leaf_shapes(dims):
    """Shapes of the row's leaves in order: each layer's weights, then its
    biases."""
    out = []
    for i, o in zip(dims[:-1], dims[1:]):
        out += [(i, o), (o,)]
    return out


def split_leaves(v, dims):
    """The leaves of rows [..., d] in order, each [..., *shape]."""
    out, o = [], 0
    for shape in leaf_shapes(dims):
        n = int(np.prod(shape))
        out.append(v[..., o:o + n].reshape(v.shape[:-1] + shape))
        o += n
    return out


@jax.jit
def state_stats(leaves, leaves0):
    """What the comparison reads of a state after the first chunk, given
    its leaves ([N, ...] each) and the shared initial row's: the norm of
    the mean row's change in each leaf ([L]), and each worker's distance
    from the mean row ([N])."""
    norms, dev2 = [], 0.0
    for x, x0 in zip(leaves, leaves0):
        x = x.astype(jnp.float32).reshape(x.shape[0], -1)
        mean = jnp.mean(x, axis=0)
        norms.append(jnp.sqrt(jnp.sum((mean - x0.reshape(-1)) ** 2)))
        dev2 = dev2 + jnp.sum((x - mean[None]) ** 2, axis=1)
    return jnp.stack(norms), jnp.sqrt(dev2)


def keyed(shapes, values):
    """[(shape, value)] in the given leaf order: the form the comparison
    matches leaves by (shape, then order among leaves of that shape)."""
    return [[list(s), float(v)] for s, v in zip(shapes, values)]


def run(cfg, seed, rounds, *, control=False, fault=None,
        noise_stream=NOISE_STREAM):
    """Follow the first ``rounds`` rounds from ``seed``. Returns each
    round's mean loss and mean pre-clip gradient norm, the state statistics
    after the last round (``state_stats``), the norm per leaf of round 0's
    mean clipped gradient, and the composed epsilon of the trajectory."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    pr, model = cfg["protocol"], cfg["model"]
    kind = model["matmul"]
    mix_kind = "float32"
    if control:
        kind, mix_kind = CONTROL[kind], CONTROL["float32"]
    N, dims = pr["workers"], tuple(layer_dims(model))
    shapes = leaf_shapes(dims)
    x, y = dataset(cfg["data"]["size"], model["input_dim"], model["classes"],
                   seed)
    pool, size = pools(partition(y, N, cfg["data"]["dirichlet_alpha"], seed))
    data = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(pool),
            jnp.asarray(size))
    del x, y
    key = jax.random.PRNGKey(seed)
    p0 = init_flat(key, dims)
    p = jnp.broadcast_to(p0[None], (N, p0.shape[0]))
    plan, rho, _ = static_channel(cfg, seed)
    plan = {k: jnp.asarray(v, jnp.float32) for k, v in plan.items()}
    noise_key = jax.random.fold_in(jax.random.PRNGKey(seed), noise_stream)
    losses, gnorms, rho_sum, g0 = [], [], 0.0, None
    for t in range(rounds):
        key, sk = jax.random.split(key)
        k_data, _ = jax.random.split(sk)
        p, loss, gn, g = _round(
            p, plan, k_data, jax.random.fold_in(noise_key, t), data,
            dims=dims, batch=pr["batch_size"], gamma=pr["gamma"],
            eta=pr["eta"], clip=pr["clip"], sigma_m=pr["sigma_m"],
            kind=kind, mix_kind=mix_kind, fault=fault)
        if t == 0:
            g0 = [jnp.sqrt(jnp.sum(a ** 2))
                  for a in split_leaves(jnp.mean(g, axis=0), dims)]
        losses.append(float(loss))
        gnorms.append(float(gn))
        if control:
            rho_sum = float(jnp.asarray(rho_sum, jnp.bfloat16)
                            + jnp.asarray(rho, jnp.bfloat16))
        else:
            rho_sum += rho
    leaf, dev = state_stats(split_leaves(p, dims), split_leaves(p0, dims))
    return {"loss": losses, "grad_norm": gnorms,
            "change_leaf": keyed(shapes, np.asarray(leaf)),
            "row_dev": np.asarray(dev, np.float64),
            "grad0_leaf": keyed(shapes, g0),
            "eps": rdp_to_eps(rho_sum, rounds, pr["delta"]),
            "rounds": rounds}
