"""repro.net — dynamic wireless network simulator (block fading, geometry,
mobility, churn) and the jit-traced per-round channel state."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dwfl, privacy
from repro.core import protocol as P
from repro.core.channel import ChannelConfig
from repro.core.exchange import make_flat_spec
from repro.net import (ChurnConfig, FadingConfig, GeometryConfig,
                       NetworkSimulator, SCENARIOS, TracedChannelState,
                       complete_mixing, get_scenario, rho_from_doppler)
from repro.net import churn as churn_lib
from repro.net import fading as fading_lib
from repro.net import geometry as geometry_lib
from repro.net.state import stack_states


# ---------------------------------------------------------------------------
# traced channel state
# ---------------------------------------------------------------------------


def test_traced_state_mirrors_static():
    """from_static preserves every derived quantity of the numpy state."""
    chan = ChannelConfig(n_workers=6, p_dbm=40.0, sigma=0.8, sigma_m=0.5,
                         seed=3).realize()
    tr = TracedChannelState.from_static(chan)
    assert tr.n_workers == chan.n_workers
    np.testing.assert_allclose(np.asarray(tr.noise_scale), chan.noise_scale,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(tr.signal_scale), chan.signal_scale,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(tr.aggregate_noise_std),
                               chan.aggregate_noise_std, rtol=1e-6)
    assert float(tr.dp_sigma) == pytest.approx(chan.dp_sigma)
    assert float(tr.awgn_sigma) == pytest.approx(chan.awgn_sigma)


def test_traced_state_is_pytree():
    chan = ChannelConfig(n_workers=4, p_dbm=40.0, seed=0).realize()
    tr = TracedChannelState.from_static(chan)
    leaves = jax.tree_util.tree_leaves(tr)
    assert len(leaves) == 7  # h P alpha beta c sigma sigma_m
    tr2 = jax.tree_util.tree_map(lambda x: x * 1.0, tr)
    assert tr2.n_workers == 4  # static metadata survives tree_map


def test_exchange_accepts_traced_channel():
    """exchange_dwfl computes the identical update for the static state and
    its traced mirror (same noise draws)."""
    N, d = 6, 32
    chan = ChannelConfig(n_workers=N, p_dbm=30.0, sigma=0.7, sigma_m=0.3,
                         seed=3).realize()
    tr = TracedChannelState.from_static(chan)
    key = jax.random.PRNGKey(0)
    X = {"w": jax.random.normal(key, (N, d))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    n_tr = dwfl.dp_noise(jax.random.fold_in(key, 1), X, tr)
    np.testing.assert_allclose(np.asarray(n["w"]), np.asarray(n_tr["w"]),
                               rtol=1e-5, atol=1e-6)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.awgn_sigma)
    want = dwfl.exchange_dwfl(X, n, m, chan, 0.4)["w"]
    got = dwfl.exchange_dwfl(X, n, m, tr, 0.4)["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_zero_retrace_across_channel_draws():
    """ACCEPTANCE: one jit-compiled DWFL step serves >= 3 distinct channel
    realizations with ZERO retraces (the channel is an argument, not a
    constant), and the realizations actually differ."""
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp

    N = 6
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=N, gamma=0.05, eta=0.5,
                             clip=1.0, channel_model="dynamic",
                             scenario="vehicular")
    cfg = get_arch("dwfl-paper").replace(d_model=32)
    sim = proto.simulator()

    traces = {"n": 0}
    key = jax.random.PRNGKey(0)
    params = mlp.init(key, cfg, input_dim=24)
    tree = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)
    spec = make_flat_spec(tree)
    inner = P.make_dynamic_flat_train_step(cfg, proto, spec.unravel_row)

    def counted(wp, batch, key, chan, W):
        traces["n"] += 1
        return inner(wp, batch, key, chan, W)

    step = jax.jit(counted)
    wp = spec.flatten(tree)
    batch = {"x": jax.random.normal(key, (N, 8, 24)),
             "y": jnp.zeros((N, 8), jnp.int32)}

    net_state = sim.init(jax.random.PRNGKey(1))
    net_round = jax.jit(sim.round)
    cs, outs = [], []
    k = jax.random.PRNGKey(2)
    for t in range(4):
        k, k1, k2 = jax.random.split(k, 3)
        net_state, chan, mask, W = net_round(k1, net_state)
        wp2, metrics = step(wp, batch, k2, chan, W)
        cs.append(float(chan.c))
        outs.append(np.asarray(wp2))
        assert np.isfinite(float(metrics["loss"]))
    assert traces["n"] == 1, f"retraced {traces['n']} times"
    assert len(set(np.round(cs, 6))) >= 3, cs       # channels really differ
    assert not np.allclose(outs[0], outs[1])        # and so do the updates


# ---------------------------------------------------------------------------
# block fading
# ---------------------------------------------------------------------------


def test_fading_ar1_correlation():
    """The diffuse component's empirical lag-1 autocorrelation across block
    boundaries matches rho."""
    cfg = FadingConfig(kind="rayleigh", rho=0.9, coherence_rounds=1)
    st = fading_lib.init_fading(cfg, jax.random.PRNGKey(0), 512)
    xs = [np.asarray(st.diffuse[:, 0])]
    k = jax.random.PRNGKey(1)
    for t in range(60):
        k, kk = jax.random.split(k)
        st = fading_lib.advance(cfg, kk, st)
        xs.append(np.asarray(st.diffuse[:, 0]))
    xs = np.stack(xs)                                # [T, N]
    x0, x1 = xs[:-1].ravel(), xs[1:].ravel()
    corr = np.corrcoef(x0, x1)[0, 1]
    assert corr == pytest.approx(0.9, abs=0.03), corr


def test_fading_block_structure():
    """Within a coherence block the gain is constant; across block edges it
    changes."""
    cfg = FadingConfig(kind="rayleigh", rho=0.3, coherence_rounds=5)
    st = fading_lib.init_fading(cfg, jax.random.PRNGKey(0), 16)
    k = jax.random.PRNGKey(1)
    hs = []
    for t in range(15):
        k, kk = jax.random.split(k)
        st = fading_lib.advance(cfg, kk, st)
        hs.append(np.asarray(fading_lib.magnitudes(cfg, st)))
    hs = np.stack(hs)  # advance happens at t_next % 5 == 0 -> rounds 5, 10, 15
    assert np.allclose(hs[0], hs[3])                 # same block
    assert not np.allclose(hs[3], hs[4])             # block edge (t_next=5)
    assert np.allclose(hs[4], hs[8])
    assert not np.allclose(hs[8], hs[9])


def test_rician_k_concentrates_gain():
    """Large K-factor -> |h| concentrates at the LOS amplitude 1."""
    cfg = FadingConfig(kind="rician", rician_k=50.0)
    st = fading_lib.init_fading(cfg, jax.random.PRNGKey(2), 2048)
    h = np.asarray(fading_lib.magnitudes(cfg, st))
    assert abs(h.mean() - 1.0) < 0.02
    assert h.std() < 0.15
    cfg_r = FadingConfig(kind="rayleigh")
    st_r = fading_lib.init_fading(cfg_r, jax.random.PRNGKey(2), 2048)
    assert np.asarray(fading_lib.magnitudes(cfg_r, st_r)).std() > h.std()


def test_on_device_alignment_matches_static_rule():
    """net.fading.align == ChannelConfig.realize's numpy alignment."""
    chan = ChannelConfig(n_workers=8, p_dbm=40.0, seed=5).realize()
    alpha, beta, c = fading_lib.align(jnp.asarray(chan.h, jnp.float32),
                                      jnp.asarray(chan.P, jnp.float32))
    np.testing.assert_allclose(np.asarray(alpha), chan.alpha, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(beta), chan.beta, rtol=1e-5)
    assert float(c) == pytest.approx(chan.c, rel=1e-5)


def test_realignment_invariants_under_fading():
    """Every realized round satisfies the paper's power constraints: the
    alignment is EXACT (signal_scale == c for all workers), alpha+beta <= 1,
    both nonnegative."""
    sim = NetworkSimulator(get_scenario("vehicular"), 12, p_dbm=65.0)
    chans, _, _ = sim.trajectory(jax.random.PRNGKey(3), 25)
    h = np.asarray(chans.h)
    alpha, beta = np.asarray(chans.alpha), np.asarray(chans.beta)
    sig = np.asarray(jax.vmap(lambda ch: ch.signal_scale)(chans))
    c = np.asarray(chans.c)[:, None]
    np.testing.assert_allclose(sig, np.broadcast_to(c, sig.shape), rtol=1e-4)
    assert (alpha > 0).all() and (alpha <= 1 + 1e-6).all()
    assert (beta >= 0).all() and (alpha + beta <= 1 + 1e-5).all()
    assert (h > 0).all()


def test_rho_from_doppler():
    assert rho_from_doppler(0.0, 1.0) == pytest.approx(1.0 - 1e-9)
    # J0 decreasing on [0, j_{0,1}): faster doppler -> less correlation
    r1, r2 = rho_from_doppler(1.0, 0.05), rho_from_doppler(5.0, 0.05)
    assert 0.0 <= r2 < r1 < 1.0
    # J0's first zero at x ~ 2.405: beyond it we clamp to 0 (decorrelated)
    assert rho_from_doppler(10.0, 0.05) == 0.0


def test_mean_descent_under_block_fading():
    """ACCEPTANCE: the DP noises cancel in the worker mean (Eqt. 9) every
    round even as the channel (and hence c and all noise amplitudes)
    re-realizes — sigma_m = 0, per-round re-alignment."""
    N, d = 8, 64
    sim = NetworkSimulator(get_scenario("vehicular"), N, p_dbm=65.0,
                           sigma=2.0, sigma_m=0.0)
    # no churn/stragglers: every worker participates (pure fading test)
    sim.scenario = dataclasses.replace(sim.scenario, churn=ChurnConfig())
    net_state = sim.init(jax.random.PRNGKey(0))
    net_round = jax.jit(sim.round)
    X = {"w": jax.random.normal(jax.random.PRNGKey(1), (N, d))}
    k = jax.random.PRNGKey(2)
    for t in range(5):
        k, k1, k2 = jax.random.split(k, 3)
        net_state, chan, mask, W = net_round(k1, net_state)
        n = dwfl.dp_noise(k2, X, chan)
        zero_m = jax.tree_util.tree_map(jnp.zeros_like, X)
        out = dwfl.exchange_dwfl_dynamic(X, n, zero_m, chan, 0.5, W)
        np.testing.assert_allclose(np.asarray(out["w"].mean(0)),
                                   np.asarray(X["w"].mean(0)),
                                   rtol=1e-4, atol=1e-5)
        X = out


def test_dynamic_exchange_reduces_to_static():
    """With the complete mixing matrix and a static traced channel, the
    dynamic exchange equals exchange_dwfl exactly."""
    N, d = 6, 40
    chan = ChannelConfig(n_workers=N, p_dbm=30.0, sigma=0.7, sigma_m=0.3,
                         seed=3).realize()
    tr = TracedChannelState.from_static(chan)
    key = jax.random.PRNGKey(0)
    X = {"w": jax.random.normal(key, (N, d))}
    n = dwfl.dp_noise(jax.random.fold_in(key, 1), X, chan)
    m = dwfl.channel_noise(jax.random.fold_in(key, 2), X, chan.awgn_sigma)
    want = dwfl.exchange_dwfl(X, n, m, chan, 0.4)["w"]
    W = complete_mixing(jnp.ones((N,), bool))
    got = dwfl.exchange_dwfl_dynamic(X, n, m, tr, 0.4, W)["w"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------


def test_path_gain_monotone_in_distance():
    cfg = GeometryConfig(pl_exponent=3.0, ref_distance=1.0,
                         normalize_gain=False)
    pos = jnp.array([[0.0, 0.0], [10.0, 0.0], [100.0, 0.0], [300.0, 0.0]])
    g = np.asarray(geometry_lib.path_gain(cfg, pos))
    d = np.abs(np.asarray(pos[:, 0]) - float(pos[:, 0].mean()))
    order = np.argsort(d)
    assert (np.diff(g[order]) <= 1e-12).all()       # farther -> weaker
    # log-distance slope: g ~ d^-3
    assert g[order][1] / g[order][2] == pytest.approx(
        (d[order][2] / d[order][1]) ** 3.0, rel=1e-3)


def test_path_gain_normalization():
    cfg = GeometryConfig(pl_exponent=3.0, normalize_gain=True)
    pos = jax.random.uniform(jax.random.PRNGKey(0), (32, 2)) * 1000.0
    g = np.asarray(geometry_lib.path_gain(cfg, pos))
    assert np.exp(np.mean(np.log(g))) == pytest.approx(1.0, rel=1e-4)
    assert g.std() > 0  # the spread survives


def test_waypoint_mobility_bounds_and_speed():
    cfg = GeometryConfig(area=100.0, mobility="waypoint", speed_min=2.0,
                         speed_max=5.0)
    st = geometry_lib.init_geometry(cfg, jax.random.PRNGKey(0), 24)
    k = jax.random.PRNGKey(1)
    for t in range(40):
        k, kk = jax.random.split(k)
        st2 = geometry_lib.advance(cfg, kk, st)
        move = np.linalg.norm(np.asarray(st2.pos - st.pos), axis=1)
        assert (move <= 5.0 + 1e-4).all()
        assert (np.asarray(st2.pos) >= 0).all()
        assert (np.asarray(st2.pos) <= 100.0).all()
        st = st2
    # workers actually moved over the run
    assert np.linalg.norm(np.asarray(st.pos), axis=1).std() > 0


def test_static_geometry_does_not_move():
    cfg = GeometryConfig(area=100.0, mobility="static")
    st = geometry_lib.init_geometry(cfg, jax.random.PRNGKey(0), 8)
    st2 = geometry_lib.advance(cfg, jax.random.PRNGKey(1), st)
    np.testing.assert_array_equal(np.asarray(st.pos), np.asarray(st2.pos))


def test_unit_disk_adjacency_and_mask():
    cfg = GeometryConfig(comm_radius=10.0)
    pos = jnp.array([[0.0, 0.0], [5.0, 0.0], [50.0, 0.0]])
    adj = np.asarray(geometry_lib.adjacency(cfg, pos))
    assert adj[0, 1] == 1 and adj[1, 0] == 1
    assert adj[0, 2] == 0 and adj[1, 2] == 0
    assert np.diag(adj).sum() == 0
    masked = np.asarray(geometry_lib.adjacency(
        cfg, pos, mask=jnp.array([True, False, True])))
    assert masked.sum() == 0  # worker 1 was the only link


def test_metropolis_weights_doubly_stochastic():
    """Metropolis weights of ANY masked random geometric graph are
    symmetric doubly stochastic; isolated workers get identity rows."""
    cfg = GeometryConfig(area=100.0, comm_radius=30.0)
    for seed in range(5):
        key = jax.random.PRNGKey(seed)
        pos = jax.random.uniform(key, (12, 2)) * 100.0
        mask = jax.random.uniform(jax.random.fold_in(key, 1), (12,)) < 0.7
        W = np.asarray(geometry_lib.metropolis_weights(
            geometry_lib.adjacency(cfg, pos, mask=mask)))
        np.testing.assert_allclose(W.sum(0), 1.0, atol=1e-6)
        np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-6)
        np.testing.assert_allclose(W, W.T, atol=1e-6)
        assert (W >= -1e-9).all()
        off = W - np.diag(np.diag(W))
        isolated = off.sum(1) < 1e-9
        assert np.allclose(np.diag(W)[isolated], 1.0)


def test_complete_mixing_matches_paper_matrix():
    N = 7
    W = np.asarray(complete_mixing(jnp.ones((N,), bool)))
    want = (np.ones((N, N)) - np.eye(N)) / (N - 1)
    np.testing.assert_allclose(W, want, atol=1e-6)
    # masked: inactive workers get identity rows, active ones average
    mask = jnp.array([True] * 4 + [False] * 3)
    Wm = np.asarray(complete_mixing(mask))
    np.testing.assert_allclose(Wm.sum(1), 1.0, atol=1e-6)
    np.testing.assert_allclose(Wm[4:, 4:], np.eye(3), atol=1e-6)
    np.testing.assert_allclose(Wm[:4, :4],
                               (np.ones((4, 4)) - np.eye(4)) / 3, atol=1e-6)


# ---------------------------------------------------------------------------
# churn
# ---------------------------------------------------------------------------


def test_churn_stationary_rate():
    cfg = ChurnConfig(p_drop=0.1, p_join=0.3)
    assert cfg.stationary_up == pytest.approx(0.75)
    st = churn_lib.init_churn(cfg, jax.random.PRNGKey(0), 4096)
    k = jax.random.PRNGKey(1)
    ups = []
    for t in range(30):
        k, kk = jax.random.split(k)
        st = churn_lib.advance(cfg, kk, st)
        ups.append(float(np.asarray(st.up).mean()))
    assert np.mean(ups) == pytest.approx(0.75, abs=0.03)


def test_churn_min_active_enforced():
    cfg = ChurnConfig(p_drop=1.0, p_join=0.0, min_active=2)
    st = churn_lib.ChurnState(up=jnp.zeros((8,), jnp.float32))
    mask = np.asarray(churn_lib.participation_mask(cfg, jax.random.PRNGKey(0),
                                                   st))
    assert mask[:2].all() and not mask[2:].any()


def test_no_churn_is_identity():
    cfg = ChurnConfig()
    st = churn_lib.init_churn(cfg, jax.random.PRNGKey(0), 16)
    assert np.asarray(st.up).all()
    st = churn_lib.advance(cfg, jax.random.PRNGKey(1), st)
    mask = churn_lib.participation_mask(cfg, jax.random.PRNGKey(2), st)
    assert np.asarray(mask).all()


# ---------------------------------------------------------------------------
# scenarios + end-to-end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_rounds_are_sane(name):
    sim = NetworkSimulator(get_scenario(name), 8, p_dbm=60.0)
    st = sim.init(jax.random.PRNGKey(0))
    rnd = jax.jit(sim.round)
    k = jax.random.PRNGKey(1)
    for t in range(4):
        k, kk = jax.random.split(k)
        st, chan, mask, W = rnd(kk, st)
        assert np.isfinite(np.asarray(chan.h)).all()
        assert float(chan.c) > 0
        assert int(np.asarray(mask).sum()) >= 2
        Wn = np.asarray(W)
        np.testing.assert_allclose(Wn.sum(1), 1.0, atol=1e-5)
        np.testing.assert_allclose(Wn.sum(0), 1.0, atol=1e-5)


def test_static_paper_scenario_is_time_invariant():
    sim = NetworkSimulator(get_scenario("static_paper"), 8, p_dbm=60.0)
    chans, masks, _ = sim.trajectory(jax.random.PRNGKey(0), 10)
    h = np.asarray(chans.h)
    assert np.allclose(h, h[0])                      # one draw, held forever
    assert np.asarray(masks).all()                   # no churn
    np.testing.assert_allclose(np.asarray(chans.c), np.asarray(chans.c)[0])


def test_dynamic_protocol_trains():
    """End-to-end: the dynamic step improves eval accuracy on the reduced
    classification task under a churning, fading network."""
    from repro.configs.registry import get_arch
    from repro.data import (FederatedBatcher, classification_dataset,
                            dirichlet_partition)
    import repro.models.mlp as mlp

    N = 8
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=N, gamma=0.02, eta=0.4,
                             clip=1.0, p_dbm=70.0, target_epsilon=1.0,
                             channel_model="dynamic", scenario="iot_dense",
                             coherence_rounds=10)
    cfg = get_arch("dwfl-paper").replace(d_model=64)
    sim = proto.simulator()
    x, y = classification_dataset(4000, input_dim=256, seed=0)
    bat = FederatedBatcher(x, y, dirichlet_partition(y, N, alpha=0.5, seed=0),
                           batch_size=32, seed=0)
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=256)
    tree = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)
    spec = make_flat_spec(tree)
    wp = spec.flatten(tree)
    step = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                  spec.unravel_row))
    net_round = jax.jit(sim.round)
    evaluate = jax.jit(P.make_eval_fn(cfg))
    _, acc0 = evaluate(spec.unravel(wp), bat.full(256))
    st = sim.init(jax.random.PRNGKey(1))
    k = jax.random.PRNGKey(2)
    for t in range(120):
        k, k1, k2 = jax.random.split(k, 3)
        st, chan, mask, W = net_round(k1, st)
        wp, metrics = step(wp, bat.next(), k2, chan, W)
    loss, acc = evaluate(spec.unravel(wp), bat.full(256))
    assert np.isfinite(float(loss))
    assert float(acc) > max(float(acc0), 0.1) + 0.05, (float(acc0), float(acc))


# ---------------------------------------------------------------------------
# privacy trajectories
# ---------------------------------------------------------------------------


def test_epsilon_traced_matches_numpy():
    chan = ChannelConfig(n_workers=8, p_dbm=40.0, sigma=0.9, sigma_m=0.4,
                         seed=7).realize()
    tr = TracedChannelState.from_static(chan)
    want = privacy.epsilon_dwfl(0.05, 1.0, chan, 1e-5)
    got = np.asarray(privacy.epsilon_dwfl_traced(0.05, 1.0, tr, 1e-5))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    s_want = privacy.sigma_for_epsilon(0.3, 0.05, 1.0, chan, 1e-5)
    s_got = float(privacy.sigma_for_epsilon_traced(0.3, 0.05, 1.0, tr, 1e-5))
    assert s_got == pytest.approx(s_want, rel=1e-5)


def test_epsilon_trajectory_shape_and_variation():
    sim = NetworkSimulator(get_scenario("vehicular"), 8, p_dbm=65.0)
    chans, _, _ = sim.trajectory(jax.random.PRNGKey(0), 20)
    eps = np.asarray(privacy.epsilon_trajectory(0.05, 1.0, chans, 1e-5))
    assert eps.shape == (20, 8)
    assert np.isfinite(eps).all() and (eps > 0).all()
    assert eps.max(1).std() > 1e-4                   # fading moves the budget


def test_per_round_calibration_pins_epsilon():
    """With target_epsilon set, the traced per-round σ calibration pins the
    worst LISTENING receiver at the target every round (unless AWGN
    over-delivers) — accounting against the round's actual masking
    neighborhoods (Ws), not the complete graph."""
    sim = NetworkSimulator(get_scenario("vehicular"), 8, p_dbm=70.0,
                           target_epsilon=0.7, gamma=0.05, clip=1.0,
                           delta=1e-5)
    chans, _, Ws = sim.trajectory(jax.random.PRNGKey(0), 15)
    eps = np.asarray(privacy.epsilon_trajectory(0.05, 1.0, chans, 1e-5, Ws))
    per_round = eps.max(1)
    assert (per_round <= 0.7 + 1e-4).all()
    assert (np.asarray(chans.sigma) > 1e-9).any()


def test_neighbor_aware_epsilon_exceeds_complete_graph():
    """Limited range + churn mean FEWER maskers per receiver: the
    neighbor-aware budgets must dominate the complete-graph formula (which
    over-credits masking noise), and isolated receivers get eps = 0."""
    chan = TracedChannelState.from_static(
        ChannelConfig(n_workers=6, p_dbm=40.0, sigma=1.0, sigma_m=0.5,
                      seed=1).realize())
    # sparse ring-ish graph + one isolated worker (5)
    adj = np.zeros((6, 6))
    for i, j in [(0, 1), (1, 2), (2, 3), (3, 4), (4, 0)]:
        adj[i, j] = adj[j, i] = 1.0
    W = geometry_lib.metropolis_weights(jnp.asarray(adj))
    eps_full = np.asarray(privacy.epsilon_dwfl_traced(0.05, 1.0, chan, 1e-5))
    eps_nb = np.asarray(privacy.epsilon_dwfl_traced(0.05, 1.0, chan, 1e-5, W))
    assert eps_nb[5] == 0.0                          # hears nothing
    assert (eps_nb[:5] >= eps_full[:5] - 1e-9).all() # fewer maskers
    assert (eps_nb[:5] > eps_full[:5]).any()
    # calibration against the sparse graph needs MORE noise
    s_full = float(privacy.sigma_for_epsilon_traced(0.3, 0.05, 1.0, chan, 1e-5))
    s_nb = float(privacy.sigma_for_epsilon_traced(0.3, 0.05, 1.0, chan, 1e-5, W))
    assert s_nb > s_full


def test_compose_heterogeneous_reduces_to_advanced():
    e, d = privacy.compose_heterogeneous([0.2] * 50, 1e-6)
    e2, d2 = privacy.compose_advanced(0.2, 1e-6, 50)
    assert e == pytest.approx(e2, rel=1e-9)
    assert d == pytest.approx(d2, rel=1e-9)
    # and it is monotone in any single round's budget
    e3, _ = privacy.compose_heterogeneous([0.2] * 49 + [0.5], 1e-6)
    assert e3 > e


def test_epsilon_report_dynamic_returns_trajectory():
    """ACCEPTANCE: epsilon_report returns per-round ε trajectories (not a
    scalar) when channel_model="dynamic"."""
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=8, gamma=0.05,
                             clip=1.0, channel_model="dynamic",
                             scenario="iot_dense")
    chans, _, Ws = proto.simulator().trajectory(jax.random.PRNGKey(0), 12)
    rep = P.epsilon_report(proto, chans, Ws=Ws)
    assert rep["epsilon_per_round"].shape == (12,)
    assert rep["rounds"] == 12
    assert rep["epsilon_worst"] == pytest.approx(rep["epsilon_per_round"].max())
    assert rep["epsilon_trajectory_composed"] > rep["epsilon_worst"]
    # static report is unchanged (scalar)
    proto_s = P.ProtocolConfig(scheme="dwfl", n_workers=8, gamma=0.05,
                               clip=1.0)
    rep_s = P.epsilon_report(proto_s, proto_s.channel())
    assert np.isscalar(rep_s["epsilon_worst"])
