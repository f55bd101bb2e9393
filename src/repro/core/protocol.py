"""Protocol configuration and the DWFL training-step factories.

Parameters live in ONE persistent [W, d] f32 buffer (exchange.FlatSpec —
ravel once at init, train flat, unravel only at eval/checkpoint). A round
is: per-worker clipped gradients (vmap over the worker rows; each
worker's loss is a function of its flat row) → local SGD step (Alg. 1
line 5) → DP noise → parameter exchange → metrics. The exchange is
dispatched through the unified mixing-matrix engine
(repro.core.exchange.resolve_spec — ONE routing table for the static and
dynamic steps). For the mixing family the whole O(d) post-gradient
pipeline is the fused dp_mix kernel (local step + on-chip noise + mixing
matmul + self-correction + AWGN in one HBM pass); the baselines, whose
specs have no fused plan, take the local step and run their element-wise
exchange on the [W, d] leaf.

Schemes:
    dwfl         — the paper's algorithm (over-the-air superposition)
    orthogonal   — pairwise transmission baseline (Remark 4.1 / Fig. 5)
    centralized  — PS over MAC baseline ([11] / Fig. 6)
    gossip       — noiseless decentralized averaging (σ = σ_m = 0 ablation)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import privacy
from repro.core import exchange as exchange_lib
from repro.core.channel import ChannelConfig, ChannelState
from repro.models import model as M


@dataclass(frozen=True)
class ProtocolConfig:
    scheme: str = "dwfl"
    n_workers: int = 16
    gamma: float = 0.05          # step size γ
    eta: float = 0.5             # averaging rate η
    clip: float = 1.0            # g_max (gradient L2 clip)
    delta: float = 1e-5
    p_dbm: float = 60.0
    sigma: float = 1.0
    sigma_m: float = 1.0
    fading: str = "rayleigh"
    seed: int = 0
    target_epsilon: float = 0.0  # >0: calibrate σ to hit this per-round ε
    participation: float = 1.0   # beyond-paper: per-round worker sampling
                                 # rate q (<1 enables privacy amplification
                                 # by subsampling; see privacy.epsilon_sampled)
    noise_policy: str = "surplus"  # channel noise-power policy (see ChannelConfig)
    topology: str = "complete"   # gossip topology: complete (the paper) |
                                 # ring | torus — limited wireless
                                 # interference ranges (repro.core.topology)
    topology_k: int = 1          # ring: neighbors per side
    channel_model: str = "static"  # static (paper: one-shot realization baked
                                   # into the compiled step) | dynamic
                                   # (repro.net: per-round traced channel —
                                   # block fading, geometry, mobility, churn)
    scenario: str = "static_paper"  # net.scenarios preset (dynamic only)
    coherence_rounds: int = 0    # >0: override the scenario's fading block
                                 # length (benchmarks sweep this)
    replicates: int = 1          # dynamic only: batch R independent network
                                 # realizations through ONE compiled step
                                 # (repro.fleet.FleetEngine; launch/train.py
                                 # --replicates)
    sparse_neighbors: int = 0    # >0: degree cap k — the dynamic per-round
                                 # W becomes a padded neighbor list
                                 # (repro.net.sparse.SparseW) and mixing,
                                 # AWGN scaling, and the graph-aware ε all
                                 # run O(N·k) instead of O(N²)
                                 # (exchange.SPECS["dynamic_sparse"];
                                 # launch/train.py --sparse-neighbors)
    graph_fallback: bool = False # bridge radius-isolated workers to their
                                 # nearest active neighbor instead of
                                 # silently training identity rows
                                 # (net.geometry; DESIGN.md §15)
    accountant: str = "composition"  # trajectory ledger for σ calibration
                                 # and report headlines: composition
                                 # (Dwork-Roth advanced) | rdp (Rényi
                                 # moments; core.accounting, DESIGN §16)
    target_total_epsilon: float = 0.0  # >0: calibrate σ once against the
                                 # FULL ``horizon``-round budget under
                                 # ``accountant`` (mutually exclusive
                                 # with target_epsilon)
    horizon: int = 0             # T for total-budget calibration (the
                                 # planned number of training rounds)

    def mixing_matrix(self):
        from repro.core import topology as topo
        return topo.make(self.topology, self.n_workers, k=self.topology_k)

    def channel(self) -> ChannelState:
        chan = ChannelConfig(
            n_workers=self.n_workers, p_dbm=self.p_dbm, sigma=self.sigma,
            sigma_m=self.sigma_m, fading=self.fading, seed=self.seed,
            noise_policy=self.noise_policy,
        ).realize()
        if self.target_epsilon > 0:
            # scheme-aware calibration: "same ε" must mean the scheme's OWN
            # worst budget. The orthogonal per-link ε and the limited-degree
            # topology ε are both much larger than the complete-graph DWFL
            # aggregate ε at equal σ (Remark 4.1 / Thm 4.1 generalized) —
            # calibrating them with the complete-graph formula would
            # silently exceed the promised budget.
            if self.scheme == "orthogonal":
                sig = privacy.sigma_for_epsilon_orthogonal(
                    self.target_epsilon, self.gamma, self.clip, chan,
                    self.delta)
            elif self.scheme == "dwfl" and self.topology != "complete":
                sig = privacy.sigma_for_epsilon_topology(
                    self.target_epsilon, self.gamma, self.clip, chan,
                    self.delta, self.mixing_matrix())
            else:
                sig = privacy.sigma_for_epsilon(
                    self.target_epsilon, self.gamma, self.clip, chan,
                    self.delta)
            chan = chan.with_sigma(max(sig, 1e-12))
        if self.target_total_epsilon > 0:
            # accountant-aware calibration against the FULL horizon: the
            # rdp ledger needs materially less σ than inverted advanced
            # composition at the same (ε_total, δ) — the end-to-end win
            # BENCH_accounting measures (core.accounting, DESIGN §16)
            from repro.core import accounting
            if self.target_epsilon > 0:
                raise ValueError("target_epsilon (per-round) and "
                                 "target_total_epsilon (horizon) are "
                                 "mutually exclusive")
            if self.horizon < 1:
                raise ValueError("target_total_epsilon needs horizon >= 1 "
                                 "(the planned number of rounds)")
            if self.scheme == "orthogonal":
                raise ValueError("total-budget calibration covers the "
                                 "mixing-family schemes only")
            W = (None if self.topology == "complete"
                 else self.mixing_matrix())
            sig = accounting.sigma_for_total_epsilon(
                self.target_total_epsilon, self.gamma, self.clip, chan,
                self.delta, self.horizon, accountant=self.accountant, W=W)
            chan = chan.with_sigma(max(sig, 1e-12))
        return chan

    def simulator(self):
        """Build the repro.net NetworkSimulator for channel_model="dynamic"
        (carries this protocol's power/noise/calibration knobs; the
        scenario contributes the radio environment)."""
        from repro.net import NetworkSimulator, get_scenario
        if self.channel_model != "dynamic":
            raise ValueError("simulator() requires channel_model='dynamic'")
        return NetworkSimulator(
            get_scenario(self.scenario), self.n_workers,
            p_dbm=self.p_dbm, sigma=self.sigma, sigma_m=self.sigma_m,
            noise_policy=self.noise_policy,
            coherence_rounds=self.coherence_rounds,
            target_epsilon=self.target_epsilon, gamma=self.gamma,
            clip=self.clip, delta=self.delta,
            sparse_k=self.sparse_neighbors,
            graph_fallback=self.graph_fallback,
            target_total_epsilon=self.target_total_epsilon,
            horizon=self.horizon, accountant=self.accountant)


def sample_participation(key, n_workers: int, q: float) -> jnp.ndarray:
    """Bool [N] transmit mask at rate q with a RANDOMIZED guaranteed pair.

    The exchange needs >= 2 transmitters to be well defined. The seed's
    guard (``mask.at[:2].set(True)``) silently made workers 0-1 transmit
    EVERY round — a fixed subset with realized rate 1, while the
    amplification accounting assumed the uniform rate q for everyone. Here
    the guaranteed pair is drawn uniformly (without replacement) from the
    round key, so the guard's extra transmissions spread evenly: every
    worker's realized rate is effective_participation(q, N) (the rate the
    report quotes; regression-tested in tests/test_dwfl.py)."""
    k_coin, k_pair = jax.random.split(key)
    mask = jax.random.uniform(k_coin, (n_workers,)) < q
    pair = jax.random.choice(k_pair, n_workers, (2,), replace=False)
    return mask.at[pair].set(True)


def effective_participation(q: float, n_workers: int) -> float:
    """Worst-case effective per-round transmit rate under the guaranteed
    pair: P(transmit) = 1 − (1−q)(1 − 2/N) = q + (1−q)·2/N, identical for
    every worker since the pair is uniform. This — not the nominal q — is
    the subsampling rate the amplification bound may use
    (privacy.epsilon_sampled)."""
    if q >= 1.0:
        return 1.0
    return q + (1.0 - q) * 2.0 / n_workers


def init_worker_params(key, cfg: ModelConfig, n_workers: int):
    """All workers start from the same point (paper: x_i^{(-1/2)} = 0; for
    NNs, a shared random init — trajectories then diverge through data and
    noise, which is what the gossip term mixes back together)."""
    params = M.init_params(key, cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), params)


def epsilon_report(proto: ProtocolConfig, chan,
                   T: Optional[int] = None, Ws=None) -> dict:
    """Privacy report. Static channel: scalar per-round budgets (the
    paper's headline numbers). Dynamic channel (channel_model="dynamic"):
    ``chan`` is a STACKED TracedChannelState trajectory (leaves [T, ...],
    from NetworkSimulator.trajectory) and the report carries the per-round
    ε TRAJECTORY plus its worst-case heterogeneous composition. Pass the
    matching per-round mixing matrices ``Ws`` ([T, N, N]) whenever the
    scenario has limited range or churn — each receiver is then credited
    only with the masking noise of workers it actually heard."""
    from repro.core import accounting
    if proto.channel_model == "dynamic":
        eps_tn = np.asarray(privacy.epsilon_trajectory(
            proto.gamma, proto.clip, chan, proto.delta, Ws))  # [T, N]
        per_round = eps_tn.max(axis=1)                        # worst receiver
        ea, da = privacy.compose_heterogeneous(per_round, proto.delta)
        # both accountants at the SAME total δ budget (= proto.delta,
        # δ-split rule): the headline keys — epsilon_total is
        # min(rdp, advanced) and never overshoots the requested δ the way
        # the legacy fixed-δ' composition above does (kept for b/c)
        both = accounting.compose_trajectory(per_round, proto.delta,
                                             delta_ref=proto.delta)
        return {
            "epsilon_per_round": per_round,
            "epsilon_worst": float(per_round.max()),
            "epsilon_mean": float(per_round.mean()),
            "epsilon_trajectory_composed": ea,
            "delta_trajectory_composed": da,
            "epsilon_advanced": float(both["epsilon_advanced"]),
            "epsilon_rdp": float(both["epsilon_rdp"]),
            "epsilon_total": float(both["epsilon"]),
            "rdp_order": float(both["rdp_order"]),
            "accountant_gap": float(both["gap_ratio"]),
            "delta_total": float(both["delta"]),
            "accountant": proto.accountant,
            "saturated": bool(both["saturated"]),
            "sigma": np.asarray(chan.sigma),
            "rounds": int(per_round.shape[0]),
        }
    eps = privacy.epsilon_dwfl(proto.gamma, proto.clip, chan, proto.delta)
    eps_orth = privacy.epsilon_orthogonal(proto.gamma, proto.clip, chan, proto.delta)
    # the headline budget is the budget of the scheme actually RUN —
    # matching the scheme-aware calibration above (an orthogonal run's
    # per-link ε, a ring/torus run's per-receiver ε), not the complete-
    # graph DWFL formula.
    if proto.scheme == "orthogonal":
        eps_scheme = eps_orth
    elif proto.scheme == "dwfl" and proto.topology != "complete":
        eps_scheme = privacy.epsilon_dwfl_topology(
            proto.gamma, proto.clip, chan, proto.delta, proto.mixing_matrix())
    else:
        eps_scheme = eps
    rep = {
        "epsilon_per_worker": eps_scheme,
        "epsilon_worst": float(eps_scheme.max()),
        "epsilon_complete_graph_worst": float(eps.max()),
        "epsilon_orthogonal_worst": float(eps_orth.max()),
        "sigma": chan.cfg.sigma,
    }
    # T-round composition starts from the budget of the scheme actually RUN
    # (eps_scheme) — composing the complete-graph eps.max() under-stated the
    # total for ring/torus and orthogonal runs, whose per-round budgets are
    # strictly larger at equal σ.
    e_round, d_round = float(eps_scheme.max()), proto.delta
    # amplification applies ONLY when the round actually samples: the
    # resolve_spec dispatch takes the sampled exchange just for the
    # complete-graph dwfl scheme (topology/orthogonal/centralized branches
    # transmit every round — quoting an amplified budget there would
    # UNDER-state the real privacy loss).
    samples = (proto.participation < 1.0 and proto.scheme == "dwfl"
               and proto.topology == "complete")
    if samples:
        # amplification uses the WORST-CASE realized rate: the randomized
        # guaranteed pair (sample_participation) lifts every worker's
        # effective rate above the nominal q.
        q_eff = effective_participation(proto.participation, proto.n_workers)
        rep["participation_nominal"] = proto.participation
        rep["participation_effective"] = q_eff
        e_round, d_round = privacy.epsilon_sampled(e_round, d_round, q_eff)
        rep["epsilon_sampled"] = e_round
    if T:
        ea, da = privacy.compose_advanced(e_round, d_round, T)
        rep["epsilon_T_advanced"], rep["delta_T_advanced"] = ea, da
        # accountant-aware T-round quotes at the SAME total δ budget
        # (= proto.delta, δ-split rule — the legacy keys above keep the
        # old fixed-δ' semantics, whose T δ + δ' total silently
        # overshoots the configured δ at large T). The RDP ledger is
        # pure in δ; with sampling it uses the subsampled-Gaussian
        # moments at the worst-case effective rate.
        d_r, d_p = accounting.split_delta(proto.delta, T)
        rho_r = accounting.rho_from_epsilon(
            float(eps_scheme.max()), proto.delta)
        if samples:
            rdp_round = accounting.rdp_subsampled_gaussian(rho_r, q_eff)
            e_split, d_split = privacy.epsilon_sampled(
                accounting.rescale_epsilon_delta(
                    float(eps_scheme.max()), proto.delta, d_r),
                d_r, q_eff)
        else:
            rdp_round = np.asarray(accounting.ORDER_GRID) * rho_r
            e_split, d_split = accounting.rescale_epsilon_delta(
                float(eps_scheme.max()), proto.delta, d_r), d_r
        ea_split, _ = privacy.compose_advanced(e_split, d_split, T, d_p)
        er, order = accounting.rdp_to_epsilon(T * rdp_round, proto.delta)
        rep["epsilon_T_advanced_split"] = ea_split
        rep["epsilon_T_rdp"] = er
        rep["epsilon_T_total"] = min(er, ea_split)
        rep["rdp_order"] = order
        rep["accountant_gap"] = ea_split / max(er, 1e-300)
        rep["delta_T_total"] = proto.delta
        rep["accountant"] = proto.accountant
        rep["saturated"] = ea_split >= privacy.EPS_SATURATION
    return rep


def _make_flat_local_pass(cfg: ModelConfig, proto: ProtocolConfig,
                          unravel_row, remat: bool = False):
    """Per-worker clipped gradients ON THE FLAT BUFFER: each worker's loss
    is a function of its flat [d] row (autodiff carries the ravel), and
    the L2 clip is one vector norm. Given ``FlatSpec.unravel_leaves`` it
    runs on a list of each worker's leaves instead (the model-sharded mesh
    round: gradients in the leaves' shapes, one norm sum per leaf).
    ``remat`` turns on the model's layer-level checkpoint (``cfg.remat``:
    each block of the layer scans is recomputed in the backward pass), so
    the saved activations are one residual-stream input per layer plus
    the one layer being differentiated (the MLP has no layer scans)."""
    clip = proto.clip
    if remat:
        cfg = cfg.replace(remat=True)

    def local_grads(flat, batch):
        def one(fv, b):
            target = lambda v: M.loss_fn(unravel_row(v), b, cfg)
            loss, g = jax.value_and_grad(target)(fv)
            g, gnorm = privacy.clip_gradient_tree(g, clip)
            return loss, g, gnorm
        with jax.named_scope("dwfl.grad"):
            return jax.vmap(one)(flat, batch)

    return local_grads


@jax.named_scope("dwfl.telemetry")
def _flat_metrics(losses, gnorms, flat):
    return {
        "loss": jnp.mean(losses),
        "grad_norm": jnp.mean(gnorms),
        "param_norm": jnp.sqrt(jnp.sum(flat.astype(jnp.float32) ** 2)),
    }


def _mixing_spec(proto: ProtocolConfig,
                 dynamic: bool) -> "exchange_lib.ExchangeSpec":
    """The spec of a round that needs the fused plan (the model- and
    worker-sharded rounds): the mixing family only."""
    spec = exchange_lib.resolve_spec(proto, dynamic=dynamic)
    if spec.plan is None:
        raise ValueError(
            f"the sharded rounds support the mixing-family exchanges only "
            f"(dwfl/gossip incl. topology/sampled/dynamic); spec "
            f"{spec.name!r} has no fused plan")
    return spec


def make_flat_train_step(cfg: ModelConfig, proto: ProtocolConfig,
                         unravel_row) -> Callable:
    """The DWFL round (STATIC channel: the one-shot realization is closed
    over as compile-time constants — the paper's setup):

        step(flat, batch, key) -> (flat', metrics)      # flat: [W, d] f32

    ``unravel_row`` maps one flat row to one worker's pytree
    (exchange.FlatSpec.unravel_row) — used only inside the grad vmap. For
    the mixing family the O(d) post-gradient pipeline is ONE fused dp_mix
    kernel call; the orthogonal and centralized baselines (no fused plan)
    take the local step and run their element-wise exchange on the
    [W, d] buffer as one leaf.
    """
    from repro.kernels.dp_mix import ops as mix_ops
    chan = proto.channel()
    spec = exchange_lib.resolve_spec(proto)
    local_grads = _make_flat_local_pass(cfg, proto, unravel_row)
    gamma, eta = proto.gamma, proto.eta

    def step(flat, batch, key):
        k_n, k_m, k_x = jax.random.split(key, 3)
        losses, g, gnorms = local_grads(flat, batch)
        if proto.n_workers < 2:
            # degenerate federation: no peers — plain local SGD
            flat = flat - gamma * g
        elif spec.plan is None:
            with jax.named_scope("dwfl.mix"):
                flat = spec.run(flat - gamma * g, (k_n, k_m, k_x), chan,
                                proto)
        else:
            with jax.named_scope("dwfl.mix"):
                plan = spec.plan(proto, chan, k_x)
            flat = mix_ops.dp_mix_round_plan(
                flat, g, mix_ops.seed_from_key(k_n), plan, gamma=gamma,
                eta=eta)
        return flat, _flat_metrics(losses, gnorms, flat)

    return step


def make_dynamic_flat_train_step(cfg: ModelConfig, proto: ProtocolConfig,
                                 unravel_row) -> Callable:
    """The DWFL round for channel_model="dynamic" (repro.net):

        step(flat, batch, key, chan, W) -> (flat', metrics)

    Unlike make_flat_train_step, the channel and mixing matrix are traced
    ARGUMENTS, not closed-over constants: ``chan`` is a
    net.TracedChannelState and ``W`` the round's mixing matrix (both from
    NetworkSimulator.round). The fused kernel takes every channel quantity
    as an operand, so ONE compiled step serves every fading block,
    geometry and churn realization — zero retraces across draws (asserted
    by tests/test_net.py and benchmarks/kernel_bench.py ``net/retrace``).
    Only scheme="dwfl" has dynamic semantics (the baselines are static-
    channel comparisons)."""
    from repro.kernels.dp_mix import ops as mix_ops
    spec = exchange_lib.resolve_spec(proto, dynamic=True)
    local_grads = _make_flat_local_pass(cfg, proto, unravel_row)
    gamma, eta = proto.gamma, proto.eta

    def step(flat, batch, key, chan, W):
        k_n, k_x = jax.random.split(key)
        losses, g, gnorms = local_grads(flat, batch)
        if proto.n_workers < 2:
            flat = flat - gamma * g
            return flat, _flat_metrics(losses, gnorms, flat)
        with jax.named_scope("dwfl.mix"):
            plan = spec.plan(proto, chan, k_x, W_arg=W)
        flat = mix_ops.dp_mix_round_plan(
            flat, g, mix_ops.seed_from_key(k_n), plan, gamma=gamma, eta=eta)
        return flat, _flat_metrics(losses, gnorms, flat)

    return step


def make_eval_fn(cfg: ModelConfig) -> Callable:
    """Per-worker eval: mean loss + mean accuracy. Accuracy is computed
    whenever the model emits classification logits against labels the
    batch actually carries (the mlp classifier's "y", explicit "labels",
    or the LM next-token targets); when it genuinely can't be defined the
    fn returns NaN — NOT a silent 0.0 that reads as a broken model."""
    def evaluate(worker_params, batch):
        def one(p, b):
            loss = M.loss_fn(p, b, cfg)
            logits, _, _ = M.forward(p, b, cfg)
            if "y" in b:                      # classifier: logits [B, C]
                acc = jnp.mean((jnp.argmax(logits, -1) == b["y"])
                               .astype(jnp.float32))
            elif "labels" in b:
                acc = jnp.mean((jnp.argmax(logits, -1) == b["labels"])
                               .astype(jnp.float32))
            elif "tokens" in b:               # LM: next-token accuracy
                acc = jnp.mean(
                    (jnp.argmax(logits[:, :-1], -1) == b["tokens"][:, 1:])
                    .astype(jnp.float32))
            else:
                acc = jnp.float32(jnp.nan)
            return loss, acc
        losses, accs = jax.vmap(one)(worker_params, batch)
        return jnp.mean(losses), jnp.mean(accs)
    return evaluate
