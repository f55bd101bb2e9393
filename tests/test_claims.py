"""Statistical paper-claims tier (pytest -m claims).

The reproduction's HEADLINE claims, finally under test: Theorem 4.1 /
Remark 4.1's per-worker privacy amplification ε = O(1/√N) across an N
grid, the orthogonal baseline's constant-in-N budget it contrasts with,
the calibration that the experiment figures imply, and the Fig. 5
accuracy claim (DWFL ≥ orthogonal at matched per-worker ε) on the
synthetic task. Everything is seeded; channel-draw randomness is averaged
over a seed grid before any slope/ratio is asserted, so the assertions
are statements about the MEAN scaling, with tolerances wide enough for
the finite grid but far too tight for a broken formula to slip through.

These tests are heavier than the unit tier (multi-seed grids, two full
training runs) and carry the ``claims`` marker: CI runs them in their own
job; the fast tier deselects them with ``-m "not claims"``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import privacy
from repro.core import protocol as P
from repro.core.exchange import make_flat_spec

pytestmark = pytest.mark.claims

N_GRID = (4, 8, 16, 32)
SEEDS = range(8)


def _proto(N, seed, *, fading="rayleigh", target_epsilon=0.0, sigma_m=1.0):
    return P.ProtocolConfig(scheme="dwfl", n_workers=N, gamma=0.02,
                            clip=1.0, sigma=1.0, sigma_m=sigma_m,
                            p_dbm=60.0, fading=fading, seed=seed,
                            target_epsilon=target_epsilon)


def _grid_mean(fn):
    """Mean of ``fn(proto, chan)`` over the seed grid, per N."""
    out = []
    for N in N_GRID:
        vals = []
        for seed in SEEDS:
            proto = _proto(N, seed)
            vals.append(fn(proto, proto.channel()))
        out.append(float(np.mean(vals)))
    return np.asarray(out)


def _loglog_slope(ns, ys):
    return float(np.polyfit(np.log(np.asarray(ns, float)),
                            np.log(np.asarray(ys, float)), 1)[0])


# ---------------------------------------------------------------------------
# Theorem 4.1 / Remark 4.1: per-worker ε scaling in N
# ---------------------------------------------------------------------------


def test_epsilon_per_worker_follows_inverse_sqrt_n_law():
    """On a homogeneous channel (unit fading — every worker contributes
    the same masking power, the regime Remark 4.1's algebra describes
    exactly) the per-worker ε from epsilon_report scales as 1/√(N−1):
    the log-log slope over the N grid is −0.5 within the grid's own
    curvature (√(N−1) vs √N bends the fit by < 0.1)."""
    eps = []
    for N in N_GRID:
        proto = _proto(N, 0, fading="unit", sigma_m=0.0)
        rep = P.epsilon_report(proto, proto.channel())
        eps.append(float(np.mean(rep["epsilon_per_worker"])))
    slope = _loglog_slope(N_GRID, eps)
    assert -0.65 < slope < -0.40, (slope, eps)
    # and the exact law, not just the trend: ε(N)/ε(4) == √(3/(N−1))
    ratio = np.asarray(eps) / eps[0]
    want = np.sqrt(3.0 / (np.asarray(N_GRID) - 1.0))
    np.testing.assert_allclose(ratio, want, rtol=1e-5)


def test_epsilon_per_worker_decreases_at_least_sqrt_n_under_fading():
    """Under the paper's Rayleigh fading the REALIZED mean per-worker ε
    decays monotonically in N and at least as fast as the 1/√N theorem
    rate (the alignment constant c also degrades with N — min over more
    draws — so the empirical slope is steeper than −0.5, never
    shallower)."""
    eps = _grid_mean(lambda proto, chan: np.mean(
        P.epsilon_report(proto, chan)["epsilon_per_worker"]))
    assert (np.diff(eps) < 0).all(), eps
    slope = _loglog_slope(N_GRID, eps)
    assert slope < -0.4, (slope, eps)


def test_remark41_bound_dominates_exact_budget():
    """The Remark 4.1 closed-form O(1/√(N−1)) bound is a true upper bound
    on the exact Theorem 4.1 budget for every worker, every N, every
    channel seed."""
    for N in N_GRID:
        for seed in SEEDS:
            proto = _proto(N, seed)
            chan = proto.channel()
            exact = privacy.epsilon_dwfl(proto.gamma, proto.clip, chan,
                                         proto.delta)
            bound = privacy.epsilon_dwfl_bound(proto.gamma, proto.clip,
                                               chan, proto.delta)
            assert (exact <= bound * (1 + 1e-9)).all(), (N, seed)


def test_orthogonal_budget_does_not_amplify_with_n():
    """Remark 4.1's contrast: the orthogonal scheme's per-link ε has NO
    1/√N amplification (each link is masked by one sender's noise only).
    Across the same grid, DWFL's mean budget shrinks by an order of
    magnitude while the orthogonal one moves by a small constant factor —
    the decay-factor gap is the figure-level claim."""
    dwfl = _grid_mean(lambda proto, chan: np.mean(
        privacy.epsilon_dwfl(proto.gamma, proto.clip, chan, proto.delta)))
    orth = _grid_mean(lambda proto, chan: np.mean(
        privacy.epsilon_orthogonal(proto.gamma, proto.clip, chan,
                                   proto.delta)))
    dwfl_decay = dwfl[0] / dwfl[-1]       # ε(N=4) / ε(N=32)
    orth_decay = orth[0] / orth[-1]
    assert orth_decay < 3.0, orth
    assert dwfl_decay > 3.0 * orth_decay, (dwfl_decay, orth_decay)


def test_calibrated_sigma_shrinks_with_n():
    """The flip side of amplification (what Figs. 3-4 sweep): holding the
    per-round target ε fixed, the calibrated DP noise σ a worker must
    inject decreases monotonically in N, at least at the 1/√N rate."""
    sig = []
    for N in N_GRID:
        vals = []
        for seed in SEEDS:
            proto = _proto(N, seed, target_epsilon=0.5, sigma_m=0.1)
            vals.append(proto.channel().cfg.sigma)
        sig.append(float(np.mean(vals)))
    assert (np.diff(sig) < 0).all(), sig
    assert _loglog_slope(N_GRID, sig) < -0.4, sig


def test_composition_sublinear_in_small_epsilon_regime():
    """The T-round budget the paper's long-horizon runs rely on: advanced
    composition beats naive T·ε in the small-per-round-ε regime the
    calibrated runs occupy, and the heterogeneous composer reduces to the
    homogeneous one on a constant trajectory."""
    e_round, delta, T = 0.05, 1e-5, 200
    e_adv, d_adv = privacy.compose_advanced(e_round, delta, T)
    e_naive, _ = privacy.compose_naive(e_round, delta, T)
    assert e_adv < e_naive, (e_adv, e_naive)
    e_het, d_het = privacy.compose_heterogeneous(
        np.full(T, e_round), delta)
    assert e_het == pytest.approx(e_adv, rel=1e-9)
    assert d_het == pytest.approx(d_adv, rel=1e-9)


def test_rdp_never_looser_than_advanced_composition():
    """ISSUE 10 acceptance: the fused Rényi ledger must quote a budget
    ≤ the δ-split advanced-composition quote AT THE SAME total δ on
    EVERY claims scenario — the full N × scheme/topology × fading static
    grid, plus a realized dynamic fading trajectory. (Both quotes are
    valid accountants of the same mechanism, so rdp > advanced would
    mean the conversion or the ledger is wrong, not the scenario.)"""
    T = 256
    for N in N_GRID:
        for scheme, topology in (("dwfl", "complete"), ("dwfl", "ring"),
                                 ("orthogonal", "complete")):
            for fading in ("rayleigh", "unit"):
                for seed in (0, 3):
                    proto = P.ProtocolConfig(
                        scheme=scheme, n_workers=N, gamma=0.02, clip=1.0,
                        sigma=1.0, sigma_m=1.0, p_dbm=60.0, fading=fading,
                        seed=seed, topology=topology, target_epsilon=0.0)
                    rep = P.epsilon_report(proto, proto.channel(), T=T)
                    ctx = (N, scheme, topology, fading, seed)
                    assert (rep["epsilon_T_rdp"]
                            <= rep["epsilon_T_advanced_split"]), ctx
                    assert rep["delta_T_total"] == proto.delta, ctx
    # dynamic: the realized per-round worst-receiver trajectory composes
    # tighter under the Rényi ledger too (trajectory-level accountants)
    from repro.core import accounting
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=8, gamma=0.02,
                             clip=1.0, sigma=1.0, sigma_m=1.0,
                             channel_model="dynamic", scenario="iot_dense",
                             target_epsilon=0.0)
    sim = proto.simulator()
    chans, _, Ws = sim.trajectory(jax.random.PRNGKey(0), 64)
    rep = P.epsilon_report(proto, chans, Ws=Ws)
    assert rep["epsilon_rdp"] <= rep["epsilon_advanced"]
    assert rep["epsilon_total"] == pytest.approx(
        min(rep["epsilon_rdp"], rep["epsilon_advanced"]))
    assert rep["delta_total"] == proto.delta
    # and the ledger is strictly tighter on this long-ish horizon
    assert rep["accountant_gap"] > 1.15


# ---------------------------------------------------------------------------
# Fig. 5: accuracy at matched per-worker privacy
# ---------------------------------------------------------------------------


def _train_accuracy(scheme, *, steps, N=8, epsilon=1.0, seed=0):
    from repro.configs.registry import get_arch
    from repro.data import (FederatedBatcher, classification_dataset,
                            dirichlet_partition)
    import repro.models.mlp as mlp

    input_dim = 256
    cfg = get_arch("dwfl-paper").replace(d_model=64)
    x, y = classification_dataset(6000, input_dim=input_dim, seed=seed)
    parts = dirichlet_partition(y, N, alpha=0.5, seed=seed)
    bat = FederatedBatcher(x, y, parts, batch_size=32, seed=seed)
    proto = P.ProtocolConfig(scheme=scheme, n_workers=N, gamma=0.02,
                             eta=0.4, clip=1.0, target_epsilon=epsilon,
                             seed=seed, p_dbm=70.0)
    key = jax.random.PRNGKey(seed)
    params = mlp.init(key, cfg, input_dim=input_dim)
    tree = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)
    spec = make_flat_spec(tree)
    flat = spec.flatten(tree)
    step = jax.jit(P.make_flat_train_step(cfg, proto, spec.unravel_row))
    for _ in range(steps):
        key, sk = jax.random.split(key)
        flat, _ = step(flat, bat.next(), sk)
    ev_loss, ev_acc = jax.jit(P.make_eval_fn(cfg))(spec.unravel(flat),
                                                   bat.full(128))
    return float(ev_loss), float(ev_acc)


def test_dwfl_accuracy_matches_orthogonal_at_matched_epsilon():
    """Fig. 5 at the claims tier: with BOTH schemes calibrated to the same
    per-worker per-round ε (scheme-aware σ — the orthogonal links need far
    more noise to hit it), DWFL's test accuracy is at least the
    orthogonal scheme's, averaged over two data/channel seeds (fixed), up
    to a 2-point tolerance; its loss is no worse either."""
    accs_d, accs_o, losses_d, losses_o = [], [], [], []
    for seed in (0, 1):
        ld, ad = _train_accuracy("dwfl", steps=300, epsilon=1.0, seed=seed)
        lo, ao = _train_accuracy("orthogonal", steps=300, epsilon=1.0,
                                 seed=seed)
        accs_d.append(ad), accs_o.append(ao)
        losses_d.append(ld), losses_o.append(lo)
    assert np.mean(accs_d) >= np.mean(accs_o) - 0.02, (accs_d, accs_o)
    assert np.mean(losses_d) <= np.mean(losses_o) + 0.05, (losses_d,
                                                           losses_o)
