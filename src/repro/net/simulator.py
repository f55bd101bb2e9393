"""NetworkSimulator: composes fading × geometry × churn into the per-round
traced channel state, participation mask and mixing matrix.

The whole per-round evolution is a pure function of (key, NetState) built
from jnp ops over [N]-shaped arrays — it jits once and serves every round
(and every realization) with zero retraces; the heavy train step consumes
its outputs as ARGUMENTS (protocol.make_dynamic_flat_train_step), so neither
side ever recompiles when the channel changes.

Round pipeline (one call to ``round``):

    fading.advance      AR(1)/Jakes block-fading clock (re-draw at block edges)
    geometry.advance    random-waypoint motion
    churn.advance       up/down Markov chain  → participation mask
    geometry.path_gain  log-distance gain to the centroid (power gain)
    fading.channel_state  |h| = |g|·√gain → on-device re-alignment (Eqt. 3-4)
    [optional]          per-round σ calibration to a target ε (traced)
    mixing matrix       masked complete graph, or Metropolis weights of the
                        masked unit-disk graph (comm_radius > 0)

``trajectory`` rolls the channel-only part T rounds via lax.scan — cheap
([N]-sized arrays) — producing the stacked TracedChannelState that
``protocol.epsilon_report`` turns into the per-round ε trajectory.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import exchange as exchange_lib
from repro.core.channel import dbm_to_watts
from repro.net import churn as churn_lib
from repro.net import fading as fading_lib
from repro.net import geometry as geometry_lib
from repro.net.scenarios import Scenario
from repro.net.state import TracedChannelState


@dataclass(frozen=True)
class NetState:
    fading: fading_lib.FadingState
    geometry: geometry_lib.GeometryState
    churn: churn_lib.ChurnState


jax.tree_util.register_dataclass(
    NetState, data_fields=["fading", "geometry", "churn"], meta_fields=[])


# Masked complete-graph mixing now lives in the unified exchange engine's
# W taxonomy (repro.core.exchange) — the simulator hands its per-round W
# and TracedChannelState straight to exchange.plan_dynamic / the fused
# dp_mix kernel; re-exported here under the historical name.
complete_mixing = exchange_lib.masked_complete_W


class NetworkSimulator:
    """Stateless orchestrator (all state lives in the NetState pytree the
    caller threads through) — safe to close over in jitted functions."""

    def __init__(self, scenario: Scenario, n_workers: int, *,
                 p_dbm: float = 60.0, sigma: float = 1.0,
                 sigma_m: float = 1.0, noise_policy: str = "surplus",
                 beta_slack: float = 1.0, coherence_rounds: int = 0,
                 target_epsilon: float = 0.0, gamma: float = 0.05,
                 clip: float = 1.0, delta: float = 1e-5,
                 sparse_k: int = 0, graph_fallback: bool = False,
                 graph_block: int = 0, target_total_epsilon: float = 0.0,
                 horizon: int = 0, accountant: str = "composition"):
        if coherence_rounds > 0:
            scenario = scenario.with_coherence(coherence_rounds)
        self.scenario = scenario
        self.n_workers = int(n_workers)
        self.P = float(dbm_to_watts(p_dbm))
        self.sigma = float(sigma)
        self.sigma_m = float(sigma_m)
        self.noise_policy = noise_policy
        self.beta_slack = float(beta_slack)
        self.target_epsilon = float(target_epsilon)
        self.gamma, self.clip, self.delta = float(gamma), float(clip), float(delta)
        # total-budget calibration (core.accounting, DESIGN §16): the
        # per-round target — an RDP rate ρ or a δ-split advanced-
        # composition ε share — is a HOST float derived once here, so the
        # traced per-round re-calibration stays a closed-over scalar
        self.target_total_epsilon = float(target_total_epsilon)
        self.accountant = accountant
        self._rho_round = self._eps_round_split = self._delta_round = None
        if self.target_total_epsilon > 0:
            from repro.core import accounting
            if self.target_epsilon > 0:
                raise ValueError("target_epsilon and target_total_epsilon "
                                 "are mutually exclusive")
            if horizon < 1:
                raise ValueError("target_total_epsilon needs horizon >= 1")
            if accountant == "rdp":
                self._rho_round = accounting.rho_total_for_epsilon(
                    self.target_total_epsilon, self.delta) / horizon
            elif accountant == "composition":
                self._eps_round_split, self._delta_round = (
                    accounting.epsilon_round_for_total_advanced(
                        self.target_total_epsilon, self.delta, horizon))
            else:
                raise ValueError(f"accountant must be 'rdp' or "
                                 f"'composition', got {accountant!r}")
        # sparse_k > 0: rounds emit a padded neighbor-list W
        # (repro.net.sparse.SparseW, degree cap k) built by the blocked
        # capped mutual-kNN ∩ unit-disk Metropolis construction — the
        # worker-scale O(N·k) representation. graph_fallback bridges
        # radius-isolated workers (geometry.sparse_metropolis / adjacency);
        # graph_block bounds the graph build's distance transient to
        # [block, N] rows (0: auto — min(1024, N)).
        self.sparse_k = int(sparse_k)
        self.graph_fallback = bool(graph_fallback)
        if self.sparse_k > self.n_workers:
            raise ValueError(f"sparse_k={sparse_k} exceeds n_workers={n_workers}")
        if self.sparse_k > 0 and scenario.geometry.comm_radius <= 0:
            # a complete graph has no k-sparse structure to exploit; the
            # builder still works (pure mutual-kNN), but require the caller
            # to opt into a geometry-limited scenario explicitly
            raise ValueError(
                "sparse_k requires a unit-disk scenario (comm_radius > 0); "
                f"scenario {scenario.name!r} has no interference radius")
        self.graph_block = (int(graph_block) if graph_block
                            else min(1024, self.n_workers))

    # -- lifecycle ---------------------------------------------------------

    def init(self, key) -> NetState:
        k_f, k_g, k_c = jax.random.split(key, 3)
        scn = self.scenario
        return NetState(
            fading=fading_lib.init_fading(scn.fading, k_f, self.n_workers),
            geometry=geometry_lib.init_geometry(scn.geometry, k_g,
                                                self.n_workers),
            churn=churn_lib.init_churn(scn.churn, k_c, self.n_workers))

    def _channel(self, state: NetState, W, P=None) -> TracedChannelState:
        scn = self.scenario
        gains = geometry_lib.path_gain(scn.geometry, state.geometry.pos)
        chan = fading_lib.channel_state(
            scn.fading, state.fading, self.P if P is None else P,
            self.sigma, self.sigma_m,
            path_gain=gains, noise_policy=self.noise_policy,
            beta_slack=self.beta_slack)
        if self.target_epsilon > 0:
            # calibrate against the round's ACTUAL masking neighborhoods
            # (limited range + churn mean fewer than N-1 maskers — the
            # complete-graph formula would under-noise the target ε).
            from repro.core import privacy
            sig = privacy.sigma_for_epsilon_traced(
                self.target_epsilon, self.gamma, self.clip, chan, self.delta,
                W)
            chan = chan.with_sigma(jnp.maximum(sig, 1e-12))
        elif self._rho_round is not None:
            # rdp total-budget calibration: hold the round at its uniform
            # RDP-rate share ρ_total/T on the realized neighborhoods
            from repro.core import accounting
            sig = accounting.sigma_for_rho_traced(
                self._rho_round, self.gamma, self.clip, chan, W)
            chan = chan.with_sigma(jnp.maximum(sig, 1e-12))
        elif self._eps_round_split is not None:
            # composition total-budget calibration: the inverted δ-split
            # advanced-composition per-round share
            from repro.core import privacy
            sig = privacy.sigma_for_epsilon_traced(
                self._eps_round_split, self.gamma, self.clip, chan,
                self._delta_round, W)
            chan = chan.with_sigma(jnp.maximum(sig, 1e-12))
        return chan

    def round(self, key, state: NetState, P=None
              ) -> Tuple[NetState, TracedChannelState, jnp.ndarray, jnp.ndarray]:
        """Advance one DWFL round. Returns (state', chan, mask, W) — all
        traced; jit this (or the train loop that calls it) once.

        ``P`` (optional, scalar or [N] watts, traced): per-call transmit-
        power override of the constructor's p_dbm. The fleet engine vmaps
        it over the replicate axis, batching a POWER SWEEP (the paper's
        Fig. 2 axis) into one compiled program."""
        k_f, k_g, k_c, k_s = jax.random.split(key, 4)
        scn = self.scenario
        state = NetState(
            fading=fading_lib.advance(scn.fading, k_f, state.fading),
            geometry=geometry_lib.advance(scn.geometry, k_g, state.geometry),
            churn=churn_lib.advance(scn.churn, k_c, state.churn))
        mask = churn_lib.participation_mask(scn.churn, k_s, state.churn)
        if self.sparse_k > 0:
            W = geometry_lib.sparse_metropolis(
                scn.geometry, state.geometry.pos, self.sparse_k, mask=mask,
                fallback=self.graph_fallback, block=self.graph_block)
        elif scn.geometry.comm_radius > 0:
            adj = geometry_lib.adjacency(scn.geometry, state.geometry.pos,
                                         mask=mask,
                                         fallback=self.graph_fallback)
            W = geometry_lib.metropolis_weights(adj)
        else:
            W = complete_mixing(mask)
        chan = self._channel(state, W, P=P)
        return state, chan, mask, W

    def trajectory(self, key, T: int, state: Optional[NetState] = None,
                   P=None
                   ) -> Tuple[TracedChannelState, jnp.ndarray, jnp.ndarray]:
        """Roll the network forward T rounds (channel-level only — no model
        work) and return the stacked per-round TracedChannelState
        ([T, ...] leaves), the [T, N] participation masks, and the
        [T, N, N] mixing matrices (a stacked [T, N, k]-leaved SparseW when
        sparse_k > 0). Feeds protocol.epsilon_report(
        channel_model="dynamic") — pass the Ws so the accounting uses the
        actual per-round masking neighborhoods."""
        if state is None:
            key, k0 = jax.random.split(key)
            state = self.init(k0)

        def body(carry, k):
            st, ch, mask, W = self.round(k, carry, P=P)
            return st, (ch, mask, W)

        keys = jax.random.split(key, T)
        _, (chans, masks, Ws) = jax.lax.scan(body, state, keys)
        return chans, masks, Ws
