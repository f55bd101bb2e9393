"""Unified mixing-matrix exchange engine (repro.core.exchange): every
ExchangeSpec against the Eqt. (8) oracle, property tests over arbitrary
doubly-stochastic W, flat-buffer mean-descent invariance, and the flat
round of every scheme (the baselines run their exchange on the [W, d]
buffer as one leaf)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypo_fallback import given, settings, st

from repro.configs.registry import get_arch
from repro.core import dwfl, exchange as X
from repro.core.channel import ChannelConfig
from repro.core.protocol import (ProtocolConfig,
                                 make_dynamic_flat_train_step,
                                 make_flat_train_step)


def _chan(N=6, sigma=0.7, sigma_m=0.3, seed=3):
    return ChannelConfig(n_workers=N, p_dbm=30.0, sigma=sigma,
                         sigma_m=sigma_m, seed=seed).realize()


def _doubly_stochastic(N, seed, terms=4):
    """Random doubly-stochastic W via Birkhoff (convex combination of
    permutation matrices) — symmetric by averaging with its transpose."""
    rng = np.random.default_rng(seed)
    lam = rng.dirichlet(np.ones(terms))
    W = np.zeros((N, N))
    for t in range(terms):
        W += lam[t] * np.eye(N)[rng.permutation(N)]
    W = 0.5 * (W + W.T)
    return W


def _draws(N, d, seed, chan):
    key = jax.random.PRNGKey(seed)
    Xt = {"w": jax.random.normal(key, (N, d))}
    G = {"w": jax.random.normal(jax.random.fold_in(key, 1), (N, d)) * 0.2}
    n = X.dp_noise(jax.random.fold_in(key, 2), Xt, chan)
    m = X.channel_noise(jax.random.fold_in(key, 3), Xt, chan.awgn_sigma)
    return Xt, G, n, m


# ---------------------------------------------------------------------------
# every ExchangeSpec vs the matrix-form oracle
# ---------------------------------------------------------------------------


def test_complete_plan_matches_reference():
    N, d, eta, gamma = 6, 40, 0.45, 0.1
    chan = _chan(N)
    Xt, G, n, m = _draws(N, d, 0, chan)
    X1 = {"w": Xt["w"] - gamma * G["w"]}
    out = X.run_mix(X1, n, m, eta, X.plan_complete(None, chan))["w"]
    ref = dwfl.matrix_form_reference(
        np.asarray(Xt["w"]), np.asarray(G["w"]), np.asarray(n["w"]),
        np.asarray(m["w"]), chan, gamma, eta)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_gossip_plan_matches_noiseless_reference():
    N, d, eta = 6, 32, 0.5
    chan = _chan(N)
    Xt, _, _, _ = _draws(N, d, 1, chan)
    zero = jax.tree_util.tree_map(jnp.zeros_like, Xt)
    out = X.run_mix(Xt, zero, zero, eta, X.plan_gossip(None, chan))["w"]
    ref = dwfl.matrix_form_reference(
        np.asarray(Xt["w"]), np.zeros((N, d)), np.zeros((N, d)),
        np.zeros((N, d)), chan, 0.0, eta)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_dynamic_plan_full_mask_matches_reference():
    from repro.net.state import TracedChannelState
    N, d, eta = 6, 24, 0.4
    chan = _chan(N)
    tr = TracedChannelState.from_static(chan)
    Xt, _, n, m = _draws(N, d, 2, chan)
    W = X.masked_complete_W(jnp.ones((N,), bool))
    out = X.run_mix(Xt, n, m, eta, X.plan_dynamic(None, tr, W_arg=W))["w"]
    ref = dwfl.matrix_form_reference(
        np.asarray(Xt["w"]), np.zeros((N, d)), np.asarray(n["w"]),
        np.asarray(m["w"]), chan, 0.0, eta)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_sampled_plan_full_participation_matches_reference():
    N, d, eta = 6, 24, 0.4
    chan = _chan(N, seed=13)
    Xt, _, n, m = _draws(N, d, 3, chan)
    plan = X.plan_sampled(
        ProtocolConfig(n_workers=N, participation=0.5), chan,
        W_arg=jnp.ones((N,), bool))
    out = X.run_mix(Xt, n, m, eta, plan)["w"]
    ref = dwfl.matrix_form_reference(
        np.asarray(Xt["w"]), np.zeros((N, d)), np.asarray(n["w"]),
        np.asarray(m["w"]), chan, 0.0, eta)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(N=st.integers(3, 9), d=st.integers(4, 64),
       eta=st.floats(0.05, 1.0), seed=st.integers(0, 10_000))
def test_property_arbitrary_doubly_stochastic_W(N, d, eta, seed):
    """PROPERTY: for ANY doubly-stochastic W, the engine equals the
    matrix-form oracle extended to that W."""
    chan = _chan(N, seed=seed % 17)
    W = _doubly_stochastic(N, seed)
    assert np.allclose(W.sum(0), 1) and np.allclose(W.sum(1), 1)
    Xt, G, n, m = _draws(N, d, seed, chan)
    gamma = 0.07
    X1 = {"w": Xt["w"] - gamma * G["w"]}
    out = X.run_mix(X1, n, m, eta,
                    X.plan_topology(None, chan, W_arg=W))["w"]
    ref = dwfl.matrix_form_reference(
        np.asarray(Xt["w"]), np.asarray(G["w"]), np.asarray(n["w"]),
        np.asarray(m["w"]), chan, gamma, eta, W=W)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(N=st.integers(3, 9), d=st.integers(8, 200),
       eta=st.floats(0.05, 1.0), seed=st.integers(0, 10_000))
def test_property_flat_buffer_mean_descent(N, d, eta, seed):
    """PROPERTY (Eqt. 9): under the fused flat-buffer round, the worker
    mean evolves EXACTLY as x̄ ← x̄ − γ ḡ for any doubly-stochastic W when
    σ_m = 0 — the on-chip DP noises cancel across receivers."""
    from repro.kernels.dp_mix import ops as mix_ops
    chan = _chan(N, sigma=1.5, seed=seed % 13)
    W = _doubly_stochastic(N, seed + 1)
    key = jax.random.PRNGKey(seed)
    p = jax.random.normal(key, (N, d))
    g = jax.random.normal(jax.random.fold_in(key, 1), (N, d)) * 0.3
    gamma = 0.05
    out = mix_ops.dp_mix_round(
        p, g, seed % 997, W, X.mix_noise_amp(chan), chan.c, 0.0,
        gamma=gamma, eta=eta,
        m_scale=X._deg_scale(jnp.asarray(W, jnp.float32), chan.c))
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray((p - gamma * g).mean(0)),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# dispatch table (the former scheme if/elif ladder)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw,want", [
    (dict(scheme="dwfl"), "complete"),
    (dict(scheme="gossip"), "gossip"),
    (dict(scheme="orthogonal"), "orthogonal"),
    (dict(scheme="centralized"), "centralized"),
    (dict(scheme="dwfl", topology="ring"), "topology"),
    (dict(scheme="dwfl", participation=0.5), "sampled"),
])
def test_resolve_spec_routing(kw, want):
    assert X.resolve_spec(ProtocolConfig(n_workers=8, **kw)).name == want


def test_resolve_spec_collective_and_dynamic():
    """The dynamic routes: the dense traced W, its neighbor-list twin, and
    the refusal of the baselines (static-channel comparisons)."""
    proto = ProtocolConfig(scheme="dwfl", n_workers=8)
    assert X.resolve_spec(proto, dynamic=True).name == "dynamic"
    assert X.resolve_spec(dataclasses.replace(proto, sparse_neighbors=3),
                          dynamic=True).name == "dynamic_sparse"
    with pytest.raises(ValueError):
        X.resolve_spec(ProtocolConfig(scheme="orthogonal", n_workers=8),
                       dynamic=True)


def test_resolve_spec_unknown_scheme():
    proto = dataclasses.replace(ProtocolConfig(n_workers=4), scheme="nope")
    with pytest.raises(ValueError):
        X.resolve_spec(proto)


# ---------------------------------------------------------------------------
# flat buffer round-trip + flat train step
# ---------------------------------------------------------------------------


def test_flatten_unravel_roundtrip():
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (4, 3, 5)),
            "b": (jax.random.normal(key, (4, 7)).astype(jnp.bfloat16),
                  jax.random.normal(key, (4,)))}
    flat = X.flatten_worker_tree(tree)
    assert flat.shape == (4, 3 * 5 + 7 + 1) and flat.dtype == jnp.float32
    unravel, unravel_row = X.worker_unravelers(tree)
    back = unravel(flat)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)
    row = unravel_row(flat[2])
    np.testing.assert_allclose(np.asarray(row["a"]),
                               np.asarray(tree["a"][2]), atol=1e-6)


def test_flatten_fleet_axes():
    key = jax.random.PRNGKey(1)
    tree = {"w": jax.random.normal(key, (3, 4, 6))}   # [R, W, d0]
    flat = X.flatten_worker_tree(tree, lead_axes=2)
    assert flat.shape == (3, 4, 6)
    unravel, unravel_row = X.worker_unravelers(tree, lead_axes=2)
    np.testing.assert_allclose(np.asarray(unravel(flat)["w"]),
                               np.asarray(tree["w"]), atol=1e-7)
    assert unravel_row(flat[1, 2]).get("w").shape == (6,)


def _mlp_problem(W=6, seed=0):
    """A tiny MLP with DIFFERENT weights on each of ``W`` workers, its
    [W, d] buffer and one batch a worker."""
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    key = jax.random.PRNGKey(seed)
    wp = jax.vmap(lambda k: mlp.init(k, cfg, input_dim=24))(
        jax.random.split(key, W))
    batch = {"x": jax.random.normal(key, (W, 8, 24)),
             "y": jax.random.randint(key, (W, 8), 0, 10)}
    return cfg, wp, batch


def _clipped_grads(cfg, wp, batch, clip):
    """Each worker's gradient of its own loss, L2-clipped, as a tree."""
    from repro.core import privacy
    from repro.models import model as M
    g = jax.vmap(jax.grad(lambda p, b: M.loss_fn(p, b, cfg)))(wp, batch)
    return jax.vmap(lambda t: privacy.clip_gradient_tree(t, clip)[0])(g)


def test_flat_step_rejects_baseline_schemes():
    """The flat step takes every scheme: orthogonal and centralized
    rounds run on the [W, d] buffer, finite and moving every row."""
    cfg, wp, batch = _mlp_problem()
    flat = X.flatten_worker_tree(wp)
    _, unravel_row = X.worker_unravelers(wp)
    for scheme in ("orthogonal", "centralized"):
        proto = ProtocolConfig(scheme=scheme, n_workers=6, gamma=0.05,
                               eta=0.5, target_epsilon=1.0)
        step = jax.jit(make_flat_train_step(cfg, proto, unravel_row))
        out, metrics = step(flat, batch, jax.random.PRNGKey(1))
        assert out.shape == flat.shape and out.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(out)))
        assert np.all(np.abs(np.asarray(out - flat)).max(axis=1) > 0)
        assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("scheme", ["orthogonal", "centralized"])
def test_flat_baseline_round_matches_leafwise_exchange(scheme):
    """σ = σ_m = 0: the flat round of a baseline equals its exchange run
    leaf by leaf on the unravelled tree after the local step (the law is
    element-wise, so the buffer's one leaf changes nothing)."""
    cfg, wp, batch = _mlp_problem()
    gamma, eta = 0.05, 0.5
    proto = ProtocolConfig(scheme=scheme, n_workers=6, gamma=gamma, eta=eta,
                           clip=1.0, sigma=0.0, sigma_m=0.0,
                           target_epsilon=0.0)
    chan = proto.channel()
    flat = X.flatten_worker_tree(wp)
    unravel, unravel_row = X.worker_unravelers(wp)
    key = jax.random.PRNGKey(2)
    got = jax.jit(make_flat_train_step(cfg, proto, unravel_row))(
        flat, batch, key)[0]

    g = _clipped_grads(cfg, wp, batch, proto.clip)
    x = jax.tree_util.tree_map(lambda p, gg: p - gamma * gg, wp, g)
    if scheme == "orthogonal":
        want = dwfl.exchange_orthogonal(x, key, chan, eta)
    else:
        zero = jax.tree_util.tree_map(jnp.zeros_like, x)
        want = dwfl.exchange_centralized(x, zero, key, chan)
    for a, b in zip(jax.tree_util.tree_leaves(unravel(got)),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("channel_model", ["static", "dynamic"])
def test_single_worker_flat_step_is_plain_sgd(channel_model):
    """One worker has no peers: the round is flat − γ·clip(g), with the
    gradient and the clip computed here by hand."""
    cfg, wp, batch = _mlp_problem(W=1)
    gamma, clip = 0.05, 0.1
    proto = ProtocolConfig(n_workers=1, gamma=gamma, clip=clip,
                           channel_model=channel_model)
    flat = X.flatten_worker_tree(wp)
    _, unravel_row = X.worker_unravelers(wp)
    key = jax.random.PRNGKey(3)
    if channel_model == "static":
        step = jax.jit(make_flat_train_step(cfg, proto, unravel_row))
        got = step(flat, batch, key)[0]
    else:
        step = jax.jit(make_dynamic_flat_train_step(cfg, proto, unravel_row))
        got = step(flat, batch, key, None, None)[0]

    from repro.models import model as M
    row = jax.grad(lambda v: M.loss_fn(unravel_row(v),
                                       {k: b[0] for k, b in batch.items()},
                                       cfg))(flat[0])
    norm = float(jnp.sqrt(jnp.sum(row ** 2)))
    assert norm > clip                       # the clip is active
    want = flat[0] - gamma * row * (clip / norm)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# property tests: FlatSpec over arbitrary pytrees × shard layouts (ISSUE 5)
# ---------------------------------------------------------------------------


def _arbitrary_worker_tree(seed: int, W: int = 4):
    """Deterministic 'arbitrary' worker-stacked pytree: nested dicts and
    tuples, mixed f32/bf16 leaves, per-worker scalar leaves (rank-0 after
    the worker axis) and occasional EMPTY subtrees."""
    rng = np.random.default_rng(seed)
    tree = {}
    for gi in range(int(rng.integers(1, 4))):
        sub = {}
        for li in range(int(rng.integers(1, 4))):
            nd = int(rng.integers(0, 3))          # 0: scalar-per-worker
            shape = (W,) + tuple(int(rng.integers(1, 7)) for _ in range(nd))
            leaf = jnp.asarray(rng.normal(size=shape).astype(np.float32))
            if rng.integers(2):
                leaf = leaf.astype(jnp.bfloat16)
            sub[f"l{li}"] = leaf
        if rng.integers(4) == 0:
            sub["empty"] = {}                     # no leaves inside
        tree[f"g{gi}"] = (sub,) if rng.integers(2) else sub
    return tree


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_shards=st.sampled_from([1, 2, 3, 4]))
def test_flat_spec_roundtrip_property(seed, n_shards):
    """flatten → unravel is EXACT for any pytree and any shard layout
    (bf16 → f32 widening is lossless, padding never overlaps a leaf), and
    the canonical columns are layout-invariant."""
    tree = _arbitrary_worker_tree(seed)
    spec = X.make_flat_spec(tree, n_shards=n_shards) if n_shards > 1 \
        else X.make_flat_spec(tree)
    flat = spec.flatten(tree)
    assert flat.shape == (4, spec.width) and flat.dtype == jnp.float32
    assert np.all(np.asarray(flat)[:, spec.d:] == 0.0)
    back = spec.unravel(flat)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(tree))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    # canonical columns do not depend on the layout
    base = X.make_flat_spec(tree).flatten(tree)
    np.testing.assert_array_equal(np.asarray(spec.unpad(flat)),
                                  np.asarray(base))
    # per-row unravel agrees with the full unravel
    row = spec.unravel_row(flat[2])
    for a, b in zip(jax.tree_util.tree_leaves(row),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32)[2])


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_shards=st.sampled_from([1, 2, 4]))
def test_grad_through_unravel_matches_tree_grad_property(seed, n_shards):
    """Autodiff carries the ravel: for any pytree and shard layout, the
    gradient of f∘unravel_row w.r.t. a worker's flat row equals the
    flattened tree gradient on that row — including exact ZEROS on the
    padding columns (they carry no parameters)."""
    tree = _arbitrary_worker_tree(seed)
    spec = X.make_flat_spec(tree, n_shards=n_shards) if n_shards > 1 \
        else X.make_flat_spec(tree)
    flat = spec.flatten(tree)

    def f_tree(t):
        return sum(jnp.sum(l.astype(jnp.float32) ** 2)
                   for l in jax.tree_util.tree_leaves(t))

    g_flat = jax.grad(lambda v: f_tree(spec.unravel_row(v)))(flat[1])
    g_tree = jax.grad(
        lambda t: f_tree(jax.tree_util.tree_map(lambda l: l[1], t)))(tree)
    want = spec.flatten(g_tree)[1]
    np.testing.assert_array_equal(np.asarray(g_flat), np.asarray(want))
    assert np.all(np.asarray(g_flat)[spec.d:] == 0.0)
