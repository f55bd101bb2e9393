"""DWFL protocol invariants (the paper's core math, Sec. IV)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dwfl
from repro.core import exchange as X_lib
from repro.core.channel import ChannelConfig
from repro.core.protocol import ProtocolConfig, make_flat_train_step
from repro.configs.registry import get_arch


def _chan(N=6, sigma=0.7, sigma_m=0.3, seed=3, fading="rayleigh"):
    return ChannelConfig(n_workers=N, p_dbm=30.0, sigma=sigma,
                         sigma_m=sigma_m, fading=fading, seed=seed).realize()


def _flat_tree(key, N, d):
    X = jax.random.normal(key, (N, d))
    return {"w": X}


def test_matrix_form_equivalence():
    """The executable per-worker update equals the paper's global matrix
    form (Eqt. 8) with the same noise realizations."""
    N, d = 6, 40
    chan = _chan(N)
    eta, gamma = 0.45, 0.1
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (N, d))
    G = jax.random.normal(jax.random.fold_in(key, 1), (N, d)) * 0.2

    X1 = {"w": X - gamma * G}  # local step applied
    noise_n = dwfl.dp_noise(jax.random.fold_in(key, 2), X1, chan)
    noise_m = dwfl.channel_noise(jax.random.fold_in(key, 3), X1,
                                 chan.cfg.sigma_m)
    out = dwfl.exchange_dwfl(X1, noise_n, noise_m, chan, eta)["w"]

    ref = dwfl.matrix_form_reference(
        np.asarray(X), np.asarray(G), np.asarray(noise_n["w"]),
        np.asarray(noise_m["w"]), chan, gamma, eta)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_mean_descent_exact_without_channel_noise():
    """Eqt. (9): with σ_m = 0 the worker mean evolves EXACTLY as
    x̄ ← x̄ − γ ḡ — the DP noises cancel across receivers."""
    N, d = 8, 64
    chan = _chan(N, sigma=2.0, sigma_m=0.0)
    eta = 0.5
    key = jax.random.PRNGKey(1)
    X1 = {"w": jax.random.normal(key, (N, d))}
    noise_n = dwfl.dp_noise(jax.random.fold_in(key, 2), X1, chan)
    zero_m = jax.tree_util.tree_map(jnp.zeros_like, X1)
    out = dwfl.exchange_dwfl(X1, noise_n, zero_m, chan, eta)["w"]
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(X1["w"].mean(0)),
                               rtol=1e-4, atol=1e-5)


def test_mean_noise_small_with_channel_noise():
    """With σ_m > 0 the mean picks up only the O(σ_m/(c N)) residual."""
    N, d = 8, 4096
    chan = _chan(N, sigma=1.0, sigma_m=1.0)
    eta = 0.5
    key = jax.random.PRNGKey(4)
    X1 = {"w": jnp.zeros((N, d))}
    noise_n = dwfl.dp_noise(jax.random.fold_in(key, 2), X1, chan)
    noise_m = dwfl.channel_noise(jax.random.fold_in(key, 3), X1, 1.0)
    out = dwfl.exchange_dwfl(X1, noise_n, noise_m, chan, eta)["w"]
    mean_dev = float(jnp.std(out.mean(0)))
    bound = eta * 1.0 / (chan.c * (N - 1)) / np.sqrt(N) * 5  # 5 sigma
    assert mean_dev < bound


def test_gossip_consensus_contraction():
    """Noiseless gossip contracts worker disagreement (spectral property of
    Ψ = (1-η)I + ηW on the complete graph)."""
    N, d = 8, 32
    chan = _chan(N, sigma=0.0, sigma_m=0.0)
    eta = 0.5
    X = {"w": jax.random.normal(jax.random.PRNGKey(5), (N, d))}
    zero = jax.tree_util.tree_map(jnp.zeros_like, X)
    var0 = float(jnp.sum(jnp.var(X["w"], axis=0)))
    out = dwfl.exchange_dwfl(X, zero, zero, chan, eta)
    var1 = float(jnp.sum(jnp.var(out["w"], axis=0)))
    # contraction factor for complete graph: (1 - eta*N/(N-1))^2
    lam = (1 - eta * N / (N - 1)) ** 2
    assert var1 <= var0 * lam * 1.01


def test_collective_path_matches_vectorized():
    """The shard_map/psum exchange computes exactly the vectorized one."""
    N, d = 4, 16
    chan = _chan(N, seed=7)
    eta = 0.4
    key = jax.random.PRNGKey(2)
    X = {"w": jax.random.normal(key, (N, d))}
    noise_n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    noise_m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.cfg.sigma_m)
    want = dwfl.exchange_dwfl(X, noise_n, noise_m, chan, eta)["w"]

    # simulate the N-worker axis with vmap over a size-N "virtual" axis —
    # the collective (psum) resolves against vmap's axis name exactly as it
    # would against a real mesh axis under shard_map.
    def per_worker(x, n, m):
        return dwfl.exchange_dwfl_collective(
            {"w": x}, {"w": n}, {"w": m}, chan, eta, "workers")["w"]
    got = jax.vmap(per_worker, axis_name="workers")(
        X["w"], noise_n["w"], noise_m["w"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_orthogonal_ring_traffic_structure():
    """The ring exchange produces the plain neighbor mean when noiseless —
    and requires N-1 permutes (structural bandwidth claim, Fig. 5/Sec. I)."""
    N, d = 5, 8
    chan = _chan(N, sigma=0.0, sigma_m=0.0)
    eta = 1.0
    X = jax.random.normal(jax.random.PRNGKey(3), (N, d))

    def per_worker(x):
        return dwfl.exchange_orthogonal_ring({"w": x}, chan, eta, "workers")["w"]
    got = jax.vmap(per_worker, axis_name="workers")(X)
    want = (jnp.sum(X, 0, keepdims=True) - X) / (N - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("scheme", ["dwfl", "orthogonal", "centralized", "gossip"])
def test_protocol_schemes_run(scheme):
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    proto = ProtocolConfig(scheme=scheme, n_workers=4, gamma=0.05, eta=0.5,
                           clip=1.0, target_epsilon=1.0)
    flat, unravel_row, batch = _flat_mlp(4)
    step = jax.jit(make_flat_train_step(cfg, proto, unravel_row))
    flat2, metrics = step(flat, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))
    assert flat2.shape == flat.shape
    assert bool(jnp.all(jnp.isfinite(flat2)))


def test_dwfl_convergence_quadratic():
    """End-to-end: DWFL drives a strongly-convex quadratic toward its
    optimum despite DP + channel noise (Thm 4.2 qualitatively)."""
    N, d = 8, 16
    proto = ProtocolConfig(scheme="dwfl", n_workers=N, gamma=0.05, eta=0.5,
                           clip=5.0, target_epsilon=2.0, seed=11)
    chan = proto.channel()
    key = jax.random.PRNGKey(0)
    # per-worker targets around a common optimum theta* (heterogeneity)
    theta_star = jax.random.normal(key, (d,))
    offsets = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (N, d))

    X = {"w": jnp.zeros((N, d))}
    eta, gamma = proto.eta, proto.gamma
    k = key
    for t in range(300):
        k, k1, k2 = jax.random.split(k, 3)
        grads = X["w"] - (theta_star + offsets)  # grad of 0.5||x - target||^2
        X1 = {"w": X["w"] - gamma * grads}
        n = dwfl.dp_noise(k1, X1, chan)
        m = dwfl.channel_noise(k2, X1, proto.sigma_m)
        X = dwfl.exchange_dwfl(X1, n, m, chan, eta)
    err = float(jnp.linalg.norm(X["w"].mean(0) - theta_star)) / np.sqrt(d)
    assert err < 0.2, err


# ---------------------------------------------------------------------------
# beyond-paper: worker sampling (privacy amplification by subsampling)
# ---------------------------------------------------------------------------


def test_sampled_exchange_full_participation_matches():
    N, d = 6, 24
    chan = _chan(N, seed=13)
    eta = 0.4
    key = jax.random.PRNGKey(6)
    X = {"w": jax.random.normal(key, (N, d))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.cfg.sigma_m)
    want = dwfl.exchange_dwfl(X, n, m, chan, eta)["w"]
    got = dwfl.exchange_dwfl_sampled(X, n, m, chan, eta,
                                     jnp.ones((N,), bool))["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_sampled_exchange_nonparticipant_invisible():
    """A non-transmitting worker's parameters/noise must not influence any
    receiver this round."""
    N, d = 5, 16
    chan = _chan(N, seed=14)
    eta = 0.5
    key = jax.random.PRNGKey(7)
    X1 = {"w": jax.random.normal(key, (N, d))}
    X2 = {"w": X1["w"].at[4].add(100.0)}  # perturb worker 4's params
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X1, chan)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X1, chan.cfg.sigma_m)
    mask = jnp.array([True, True, True, True, False])
    out1 = dwfl.exchange_dwfl_sampled(X1, n, m, chan, eta, mask)["w"]
    out2 = dwfl.exchange_dwfl_sampled(X2, n, m, chan, eta, mask)["w"]
    # receivers 0..3 see identical updates; worker 4's own row differs
    np.testing.assert_allclose(np.asarray(out1[:4] - out2[:4]), 0.0, atol=1e-5)
    assert float(jnp.max(jnp.abs(out1[4] - out2[4]))) > 1.0


def test_sampled_privacy_amplification():
    from repro.core import privacy
    e, d = privacy.epsilon_sampled(0.8, 1e-5, 0.3)
    assert e < 0.8 * 0.5  # roughly q*eps for small eps
    assert d == pytest.approx(0.3e-5)
    e1, _ = privacy.epsilon_sampled(0.8, 1e-5, 1.0)
    assert e1 == pytest.approx(0.8)


def test_sampled_all_but_two_out():
    """Edge case: exactly two transmitters. Each transmitter sees only the
    OTHER transmitter (denominator 1); pure receivers average the two."""
    N, d = 6, 12
    chan = _chan(N, sigma=0.0, sigma_m=0.0, seed=15)
    eta, c = 0.5, chan.c
    key = jax.random.PRNGKey(8)
    X = {"w": jax.random.normal(key, (N, d))}
    zero = jax.tree_util.tree_map(jnp.zeros_like, X)
    mask = jnp.array([True, True, False, False, False, False])
    out = dwfl.exchange_dwfl_sampled(X, zero, zero, chan, eta, mask)["w"]
    x = np.asarray(X["w"])
    # transmitter 0 hears only transmitter 1 (and vice versa)
    np.testing.assert_allclose(np.asarray(out[0]),
                               x[0] + eta * (x[1] - x[0]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]),
                               x[1] + eta * (x[0] - x[1]), rtol=1e-5)
    # pure receivers mix toward the transmitter mean (n_tx - 0 = 2 visible)
    for i in range(2, N):
        np.testing.assert_allclose(
            np.asarray(out[i]),
            x[i] + eta * ((x[0] + x[1]) / 2.0 - x[i]), rtol=1e-5)


def test_sampled_denominator_clamping_degenerate():
    """Below the protocol's guaranteed minimum (a single transmitter — can
    only arise if a caller bypasses the >=2 guard) the clamps n_tx>=2 and
    denom>=1 keep every update finite and bounded."""
    N, d = 5, 8
    chan = _chan(N, seed=16)
    key = jax.random.PRNGKey(9)
    X = {"w": jax.random.normal(key, (N, d))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.cfg.sigma_m)
    for n_tx in (0, 1):
        mask = jnp.arange(N) < n_tx
        out = dwfl.exchange_dwfl_sampled(X, n, m, chan, 0.5, mask)["w"]
        assert bool(jnp.all(jnp.isfinite(out)))
        # the sole transmitter hears nobody: only its own-noise correction
        # and the AWGN term remain, both bounded
        assert float(jnp.max(jnp.abs(out))) < 1e3


def _flat_mlp(N, seed=0):
    """[N, d] buffer of a tiny MLP (a shared init, as the trainer starts),
    its row unraveler and one batch a worker."""
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    key = jax.random.PRNGKey(seed)
    params = mlp.init(key, cfg, input_dim=24)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)
    spec = X_lib.make_flat_spec(wp)
    batch = {"x": jax.random.normal(key, (N, 8, 24)),
             "y": jax.random.randint(key, (N, 8), 0, 10)}
    return spec.flatten(wp), spec.unravel_row, batch


@pytest.mark.parametrize("kw", [
    dict(scheme="dwfl"), dict(scheme="gossip"),
    dict(scheme="dwfl", topology="ring"),
    dict(scheme="dwfl", participation=0.5)],
    ids=["complete", "gossip", "topology", "sampled"])
def test_flat_step_mean_invariant_every_mixing_spec(kw):
    """Eqt. 9 with σ_m = 0 on each plan of the fused flat round: the DP
    noise cancels in the worker mean, which moves by −γ·mean(clip(g))
    alone. The mean is the one the round's W keeps: uniform for the
    doubly-stochastic plans, uniform over the transmitters for the
    sampled one (whose listeners' columns are zero)."""
    from repro.core import privacy
    from repro.models import model as M
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    N, gamma = 6, 0.05
    proto = ProtocolConfig(n_workers=N, gamma=gamma, eta=0.5, clip=1.0,
                           target_epsilon=1.0, sigma_m=0.0, **kw)
    flat, unravel_row, batch = _flat_mlp(N)
    flat = flat + 0.1 * jax.random.normal(jax.random.PRNGKey(5), flat.shape)
    key = jax.random.PRNGKey(7)
    out = jax.jit(make_flat_train_step(cfg, proto, unravel_row))(
        flat, batch, key)[0]

    spec = X_lib.resolve_spec(proto)
    assert spec.plan is not None
    W = np.asarray(spec.plan(proto, proto.channel(),
                             jax.random.split(key, 3)[2]).W, np.float64)
    vals, vecs = np.linalg.eig(W.T)
    pi = np.real(vecs[:, np.argmin(np.abs(vals - 1.0))])
    pi = pi / pi.sum()
    g = jax.vmap(lambda v, b: privacy.clip_gradient_tree(
        jax.grad(lambda u: M.loss_fn(unravel_row(u), b, cfg))(v),
        proto.clip)[0])(flat, batch)
    want = pi @ np.asarray(flat - gamma * g, np.float64)
    np.testing.assert_allclose(pi @ np.asarray(out, np.float64), want,
                               rtol=1e-4, atol=2e-5)
    # the round did mix: rows moved by more than the local step
    assert float(jnp.max(jnp.abs(out - (flat - gamma * g)))) > 1e-3


def test_sampled_protocol_runs():
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    proto = ProtocolConfig(scheme="dwfl", n_workers=6, gamma=0.05, eta=0.5,
                           clip=1.0, target_epsilon=1.0, participation=0.5)
    flat, unravel_row, batch = _flat_mlp(6)
    step = jax.jit(make_flat_train_step(cfg, proto, unravel_row))
    flat2, metrics = step(flat, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))
    assert bool(jnp.all(jnp.isfinite(flat2)))


# ---------------------------------------------------------------------------
# ISSUE 2 regressions: randomized guaranteed pair, orthogonal deep-fade floor
# ---------------------------------------------------------------------------


def test_sampled_mask_no_fixed_subset():
    """Regression (ISSUE 2): the >=2-transmitters guard must not pin a FIXED
    worker pair (the seed's mask.at[:2].set(True) made workers 0-1 transmit
    every round at realized rate 1 while the amplification accounting
    assumed rate q). With the randomized pair: no worker transmits in every
    round, every round still has >= 2 transmitters, and each worker's
    realized frequency matches the effective rate the report quotes."""
    from repro.core.protocol import (effective_participation,
                                     sample_participation)
    N, q, T = 8, 0.3, 2000
    keys = jax.random.split(jax.random.PRNGKey(0), T)
    masks = np.asarray(jax.vmap(
        lambda k: sample_participation(k, N, q))(keys))
    assert masks.shape == (T, N)
    assert (masks.sum(axis=1) >= 2).all()          # round stays well defined
    rates = masks.mean(axis=0)                     # realized per-worker rate
    assert rates.max() < 1.0                       # no always-on subset
    q_eff = effective_participation(q, N)
    # every worker's realized rate within 5 sigma of the quoted effective
    # rate (binomial std over T rounds) — workers 0-1 no longer special
    tol = 5.0 * np.sqrt(q_eff * (1.0 - q_eff) / T)
    assert np.abs(rates - q_eff).max() < tol, (rates, q_eff, tol)


def test_sampled_report_quotes_effective_rate():
    """epsilon_report must amplify with the worst-case EFFECTIVE rate
    (nominal q + the guaranteed-pair lift), and that rate must match the
    realized transmit frequency of the actual mask sampler."""
    from repro.core import privacy
    from repro.core.protocol import (ProtocolConfig, effective_participation,
                                     epsilon_report, sample_participation)
    N, q = 8, 0.3
    proto = ProtocolConfig(scheme="dwfl", n_workers=N, participation=q)
    rep = epsilon_report(proto, proto.channel(), T=10)
    q_eff = effective_participation(q, N)
    assert rep["participation_nominal"] == q
    assert rep["participation_effective"] == pytest.approx(q_eff)
    assert q < q_eff < 1.0
    # the quoted amplified budget uses q_eff, not the nominal q
    want_e, _ = privacy.epsilon_sampled(rep["epsilon_worst"], proto.delta,
                                        q_eff)
    assert rep["epsilon_sampled"] == pytest.approx(want_e)
    # and q_eff is the realized frequency of the sampler itself
    T = 4000
    masks = np.asarray(jax.vmap(
        lambda k: sample_participation(k, N, q)
    )(jax.random.split(jax.random.PRNGKey(1), T)))
    realized = masks.mean()
    assert abs(realized - q_eff) < 5.0 * np.sqrt(q_eff * (1 - q_eff) / (T * N))


def test_orthogonal_deep_fade_bounded():
    """Regression (ISSUE 2): a deep-fade draw (|h| -> 0) used to send the
    inverted per-link gain to 0 and the link-AWGN std to infinity. The
    documented floor (dwfl.ORTHOGONAL_GAIN_FLOOR relative to the best link)
    keeps the exchange finite and bounded."""
    chan = _chan(N=6, seed=21)
    # force worker 3 into deep fade; keep alpha/P as realized so the
    # inverted gain h*sqrt(alpha*P) collapses for that link
    h = np.array(chan.h)
    h[3] = 1e-12
    deep = dataclasses.replace(chan, h=h)
    key = jax.random.PRNGKey(5)
    X = {"w": jax.random.normal(key, (6, 16))}
    out = dwfl.exchange_orthogonal(X, key, deep, 0.4)["w"]
    assert bool(jnp.all(jnp.isfinite(out)))
    # bounded: the floored link inflates noise by at most 1/GAIN_FLOOR
    # relative to the healthy links — not by 1e12
    assert float(jnp.max(jnp.abs(out))) < 1e4


def test_eval_fn_lm_next_token_accuracy():
    """Regression (ISSUE 3): make_eval_fn silently returned acc=0.0 for
    every non-mlp family. LM families now report true next-token accuracy
    (verified against a manual forward), not a hardcoded zero."""
    from repro.core.protocol import make_eval_fn
    from repro.models import model as M
    cfg = get_arch("olmo-1b").reduced().replace(vocab_size=8)
    key = jax.random.PRNGKey(0)
    W, B, S = 2, 2, 32
    params = M.init_params(key, cfg)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (W, B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    loss, acc = make_eval_fn(cfg)(wp, batch)
    assert np.isfinite(float(loss))
    want = np.mean([
        np.mean(np.argmax(np.asarray(
            M.forward(params, {"tokens": tokens[w]}, cfg)[0])[:, :-1], -1)
            == np.asarray(tokens[w])[:, 1:])
        for w in range(W)])
    assert float(acc) == pytest.approx(want, abs=1e-6)
    assert 0.0 <= float(acc) <= 1.0


def test_eval_fn_mlp_accuracy_nonzero_when_learnable():
    """The mlp branch keeps returning true accuracy (and a perfectly
    separable batch scores 1.0 after enough signal — sanity that the
    refactored eval still reads logits)."""
    from repro.core.protocol import make_eval_fn
    import repro.models.mlp as mlp
    cfg = get_arch("dwfl-paper").replace(d_model=16)
    key = jax.random.PRNGKey(0)
    params = mlp.init(key, cfg, input_dim=4)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (2,) + a.shape), params)
    x = jax.random.normal(key, (2, 16, 4))
    batch = {"x": x, "y": jnp.zeros((2, 16), jnp.int32)}
    loss, acc = make_eval_fn(cfg)(wp, batch)
    assert np.isfinite(float(loss)) and 0.0 <= float(acc) <= 1.0


def test_sampled_report_not_amplified_off_sampled_path():
    """Amplification must NOT be quoted for configs whose dispatch never
    reaches the sampled exchange (ring topology / orthogonal transmit every
    round regardless of `participation`) — quoting it would UNDER-state the
    real budget."""
    from repro.core import privacy
    from repro.core.protocol import ProtocolConfig, epsilon_report
    for kw in (dict(scheme="dwfl", topology="ring"),
               dict(scheme="orthogonal"),):
        proto = ProtocolConfig(n_workers=8, participation=0.3, **kw)
        rep = epsilon_report(proto, proto.channel(), T=10)
        assert "epsilon_sampled" not in rep
        assert "participation_effective" not in rep
        want, _ = privacy.compose_advanced(rep["epsilon_worst"],
                                           proto.delta, 10)
        assert rep["epsilon_T_advanced"] == pytest.approx(want)
