"""Gossip-topology generalization (beyond-paper; the paper's Lemmas 4.3/4.4
already assume a general doubly-stochastic W_eff)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:  # hypothesis is optional offline (see tests/_hypo_fallback.py)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypo_fallback import given, settings, st

from repro.core import dwfl, privacy
from repro.core import topology as topo
from repro.core.channel import ChannelConfig


def _chan(N, **kw):
    base = dict(n_workers=N, p_dbm=40.0, sigma=0.5, sigma_m=0.2, seed=3)
    base.update(kw)
    return ChannelConfig(**base).realize()


@pytest.mark.parametrize("kind,kw", [("complete", {}), ("ring", {"k": 1}),
                                     ("ring", {"k": 2}), ("torus", {})])
def test_mixing_matrices_doubly_stochastic(kind, kw):
    W = topo.make(kind, 12 if kind != "torus" else 12, **kw)
    assert topo.check_doubly_stochastic(W)
    assert np.allclose(np.diag(W), 0.0)


def test_complete_graph_reduces_to_paper_exchange():
    N, d = 6, 32
    chan = _chan(N)
    eta = 0.45
    key = jax.random.PRNGKey(0)
    X = {"w": jax.random.normal(key, (N, d))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.cfg.sigma_m)
    want = dwfl.exchange_dwfl(X, n, m, chan, eta)["w"]
    got = dwfl.exchange_dwfl_topology(X, n, m, chan, eta, topo.complete(N))["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["ring", "torus"])
def test_mean_descent_holds_on_sparse_topologies(kind):
    """The DP-noise zero-sum across receivers needs only doubly-stochastic W."""
    N = 9
    chan = _chan(N, sigma_m=0.0)
    W = topo.make(kind, N)
    key = jax.random.PRNGKey(1)
    X = {"w": jax.random.normal(key, (N, 64))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    zero_m = jax.tree_util.tree_map(jnp.zeros_like, X)
    out = dwfl.exchange_dwfl_topology(X, n, zero_m, chan, 0.5, W)["w"]
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(X["w"].mean(0)),
                               rtol=1e-4, atol=1e-5)


def test_contraction_matches_spectral_prediction():
    N = 8
    W = topo.ring(N, k=1)
    eta = topo.optimal_eta(W)
    lam = topo.contraction(W, eta)
    chan = _chan(N, sigma=0.0, sigma_m=0.0)
    key = jax.random.PRNGKey(2)
    X = {"w": jax.random.normal(key, (N, 128))}
    zero = jax.tree_util.tree_map(jnp.zeros_like, X)
    # run 10 noiseless rounds; disagreement decays ~ lam^t (up to the
    # non-normal transient, bounded by a small factor)
    var0 = float(jnp.sum(jnp.var(X["w"], 0)))
    for _ in range(10):
        X = dwfl.exchange_dwfl_topology(X, zero, zero, chan, eta, W)
    var10 = float(jnp.sum(jnp.var(X["w"], 0)))
    assert var10 <= var0 * (lam ** (2 * 10)) * 3.0
    assert var10 >= var0 * (lam ** (2 * 10)) * 0.01


def test_complete_contracts_faster_than_ring():
    N = 16
    for eta_kind in ("optimal",):
        Wc, Wr = topo.complete(N), topo.ring(N, 1)
        lc = topo.contraction(Wc, topo.optimal_eta(Wc))
        lr = topo.contraction(Wr, topo.optimal_eta(Wr))
        assert lc < lr  # complete graph mixes faster


def test_topology_privacy_interpolates():
    """ε scales ~1/sqrt(deg): ring(k=1, deg 2) sits between orthogonal
    (deg 1) and complete (deg N-1)."""
    N = 16
    chan = ChannelConfig(n_workers=N, p_dbm=40.0, sigma=1.0, sigma_m=1.0,
                         fading="unit", seed=0).realize()
    g, gm, d = 0.05, 1.0, 1e-5
    e_complete = privacy.epsilon_dwfl_topology(g, gm, chan, d, topo.complete(N)).max()
    e_ring = privacy.epsilon_dwfl_topology(g, gm, chan, d, topo.ring(N, 1)).max()
    e_orth = privacy.epsilon_orthogonal(g, gm, chan, d).max()
    assert e_complete < e_ring < e_orth
    # deg-based prediction: ring/complete ~ sqrt((N-1)/2) up to sigma_m terms
    s2 = float(chan.noise_scale[0] ** 2)
    want = np.sqrt((15 * s2 + 1) / (2 * s2 + 1))
    assert e_ring / e_complete == pytest.approx(want, rel=0.02)


def test_protocol_with_ring_topology_runs():
    from repro.core.exchange import make_flat_spec
    from repro.core.protocol import ProtocolConfig, make_flat_train_step
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    proto = ProtocolConfig(scheme="dwfl", n_workers=6, gamma=0.05, eta=0.5,
                           clip=1.0, target_epsilon=1.0, topology="ring")
    key = jax.random.PRNGKey(0)
    params = mlp.init(key, cfg, input_dim=24)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (6,) + a.shape), params)
    spec = make_flat_spec(wp)
    step = jax.jit(make_flat_train_step(cfg, proto, spec.unravel_row))
    batch = {"x": jax.random.normal(key, (6, 8, 24)),
             "y": jnp.zeros((6, 8), jnp.int32)}
    flat2, metrics = step(spec.flatten(wp), batch, key)
    assert np.isfinite(float(metrics["loss"]))
    assert bool(jnp.all(jnp.isfinite(flat2)))


@settings(max_examples=15, deadline=None)
@given(N=st.integers(4, 24), k=st.integers(1, 3))
def test_property_ring_spectrum(N, k):
    k = min(k, (N - 1) // 2)
    if k < 1:
        return
    W = topo.ring(N, k)
    assert topo.check_doubly_stochastic(W)
    eta = topo.optimal_eta(W)
    assert 0.0 < eta <= 1.0
    assert topo.contraction(W, eta) < 1.0  # connected -> contracts
