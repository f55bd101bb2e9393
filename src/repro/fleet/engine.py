"""FleetEngine — batched multi-replicate DWFL simulation.

PR 1 made the dynamic channel a traced ARGUMENT of the compiled round, so
one executable serves every realization of one network. This module adds
the missing axis: a leading REPLICATE axis R, vmapped over everything the
round consumes — stacked ``NetState``/``TracedChannelState`` pytrees
([R, ...] leaves), stacked [R, N, N] mixing matrices, per-replicate PRNG
keys, per-replicate worker params [R, W, ...] and batches [R, W, B, ...].
One compiled step then advances R INDEPENDENT (seed × scenario-variant)
networks at once — the batched-replicate scenario-evaluation pattern of
decentralized-FL mesh simulators (cf. arXiv 2311.01186), with three wins
over the R-iteration Python loop it replaces:

  * dispatch amortization: 1 jitted call per round instead of 2R,
  * fusion: XLA batches R tiny matmuls/reductions into one kernel each,
  * zero retraces across replicate BATCHES (the [R, ...] shapes are fixed;
    fresh stacked realizations are just new arguments — asserted by the
    ``fleet/retrace`` kernel-bench case and tests/test_fleet.py).

Replicates are i.i.d. ONLY through their PRNG keys (fading, placement,
churn, data order, DP/channel noise); the scenario preset, worker count and
protocol knobs are shared — except transmit power, which may be a per-
replicate [R] array (``power_dbm``), folding the paper's Fig. 2 power-sweep
axis into the same compiled program. An optional ``shard_map`` path
(``make_fleet_step(..., mesh=...)``) shards the replicate axis over mesh
devices: replicates are embarrassingly parallel, so the sharded program is
the vmapped one with R/|mesh| replicates per device and no cross-device
collectives. See DESIGN.md §repro.fleet.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import exchange as exchange_lib
from repro.core import protocol as protocol_lib
from repro.core.channel import dbm_to_watts
from repro.net.simulator import NetState
from repro.net.state import TracedChannelState


def stack_rounds(rounds):
    """Stack a per-round list of [R, ...]-leaved pytrees along a NEW axis 1:
    the [R, T, ...] layout consumed by privacy.epsilon_trajectory_batched
    (axis 0 stays the replicate axis, matching FleetEngine.trajectory)."""
    rounds = list(rounds)
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=1), *rounds)


def mean_ci(values, confidence_z: float = 1.96):
    """Across-replicate aggregate: (mean, half-width of the normal-approx
    95% CI of the mean). One replicate ⇒ CI 0 (no spread information)."""
    v = np.asarray(values, np.float64).reshape(-1)
    if v.size <= 1:
        return float(v.mean()), 0.0
    return (float(v.mean()),
            float(confidence_z * v.std(ddof=1) / np.sqrt(v.size)))


class FleetEngine:
    """Batched (vmapped) front end of net.NetworkSimulator + the dynamic
    train step: every method takes/returns pytrees with a leading replicate
    axis R. Stateless like the simulator it wraps — jit-safe to close over.

    ``power_dbm``: None (all replicates use proto.p_dbm) or an [R] array of
    per-replicate transmit powers (the scenario-variant axis).
    """

    def __init__(self, proto: "protocol_lib.ProtocolConfig",
                 replicates: Optional[int] = None, *, power_dbm=None):
        if proto.channel_model != "dynamic":
            raise ValueError("FleetEngine requires channel_model='dynamic' "
                             "(the static channel is baked into the compiled "
                             "step — there is nothing to batch)")
        self.proto = proto
        self.replicates = int(replicates if replicates is not None
                              else proto.replicates)
        if self.replicates < 1:
            raise ValueError(f"replicates must be >= 1, got {self.replicates}")
        self.sim = proto.simulator()
        if power_dbm is None:
            self._P = None                      # shared proto.p_dbm
        else:
            p = np.asarray(power_dbm, np.float64).reshape(-1)
            if p.shape[0] != self.replicates:
                raise ValueError(f"power_dbm has {p.shape[0]} entries for "
                                 f"{self.replicates} replicates")
            self._P = jnp.asarray(dbm_to_watts(p), jnp.float32)  # [R] watts

    # -- network lifecycle (all [R, ...]-leaved) ---------------------------

    def split_keys(self, key) -> jnp.ndarray:
        """[R] independent per-replicate keys from one fleet key."""
        return jax.random.split(key, self.replicates)

    def init(self, key) -> NetState:
        """Stacked initial NetState: leaves [R, ...] — replicate r is
        bitwise sim.init(split(key)[r]) (the loop-equivalence anchor)."""
        return jax.vmap(self.sim.init)(self.split_keys(key))

    def round(self, key, states: NetState
              ) -> Tuple[NetState, TracedChannelState, jnp.ndarray, jnp.ndarray]:
        """Advance all R networks one round. Returns (states', chans, masks,
        Ws) with leaves [R, ...] / [R, N] / [R, N, N]."""
        keys = self.split_keys(key)
        if self._P is None:
            return jax.vmap(self.sim.round)(keys, states)
        return jax.vmap(lambda k, s, p: self.sim.round(k, s, P=p))(
            keys, states, self._P)

    def trajectory(self, key, T: int, states: Optional[NetState] = None
                   ) -> Tuple[TracedChannelState, jnp.ndarray, jnp.ndarray]:
        """R stacked T-round channel trajectories: ([R, T, ...] chans,
        [R, T, N] masks, [R, T, N, N] Ws) — the direct input to
        privacy.epsilon_trajectory_batched."""
        keys = self.split_keys(key)
        if states is None:
            if self._P is None:
                return jax.vmap(lambda k: self.sim.trajectory(k, T))(keys)
            return jax.vmap(
                lambda k, p: self.sim.trajectory(k, T, P=p))(keys, self._P)
        if self._P is None:
            return jax.vmap(
                lambda k, s: self.sim.trajectory(k, T, state=s))(keys, states)
        return jax.vmap(
            lambda k, s, p: self.sim.trajectory(k, T, state=s, P=p)
        )(keys, states, self._P)

    # -- model side --------------------------------------------------------

    def init_worker_params(self, key, cfg):
        """[R, W, ...] params: replicate r's W workers share ONE init drawn
        from key_r (the paper's common-start rule, independently per
        network)."""
        return jax.vmap(
            lambda k: protocol_lib.init_worker_params(k, cfg, self.proto.n_workers)
        )(self.split_keys(key))

    def init_flat_spec(self, key, cfg, n_shards: int = 1,
                       max_chunk_cols=None):
        """Flat-buffer fleet params as ([R, W, width] f32 buffer,
        exchange.FlatSpec). Raveled ONCE here; ``n_shards`` > 1 attaches a
        model-axis ShardLayout (repro.shard) — the buffer is then padded
        to the layout's physical width and usable with the sharded fleet
        step (2-D replicas×model mesh, or logically on one device).
        ``max_chunk_cols`` caps the gather-free grad pass's per-collective
        chunk width (spec.chunk_plan); ignored when unsharded."""
        wp = self.init_worker_params(key, cfg)
        spec = exchange_lib.make_flat_spec(wp, lead_axes=2,
                                           n_shards=n_shards,
                                           max_chunk_cols=max_chunk_cols)
        return spec.flatten(wp), spec

    def init_flat_params(self, key, cfg):
        """Legacy tuple API: ([R, W, d] f32 buffer, unravel, unravel_row)
        — init_flat_spec without the layout handle."""
        flat, spec = self.init_flat_spec(key, cfg)
        return flat, spec.unravel, spec.unravel_row

    def make_fleet_step(self, cfg, mesh=None, axis: str = "replicas",
                        unravel_row=None, spec=None, remat: bool = False):
        """The batched round:

            step(flat, batch, keys, chans, Ws)
                -> (flat', metrics)              # every leaf [R, ...]

        vmap of protocol.make_dynamic_flat_train_step over the replicate
        axis: ``flat`` is the [R, W, d] buffer (init_flat_spec — pass its
        ``spec``, or the ``unravel_row`` from init_flat_params) and the
        whole per-replicate O(d) pipeline is one vmapped dp_mix kernel
        call. With ``mesh`` (optional, 1-axis jax mesh), the same program
        is wrapped in shard_map instead, splitting R over the mesh devices
        (R % |mesh| must be 0); replicates never communicate, so in/out
        specs are plain leading-axis shards and the body stays the vmapped
        step on the local R/|mesh| slab.

        Pass a model-sharded ``spec`` (FleetEngine.init_flat_spec with
        n_shards > 1) to shard each replicate's buffer columns as well
        (repro.shard): with a 2-D ("replicas", "model") mesh the step is
        the 2-D shard_map (replicates × buffer columns); with mesh=None or
        a replicas-only mesh the model axis is sharded LOGICALLY inside
        each device's program. The sharded fleet round is ULP-close (not
        bitwise) to the unsharded one: the R-vmapped dp_mix matmul lands
        in different XLA fusion clusters (same caveat as the scan engine,
        DESIGN.md §10).
        """
        if spec is not None and spec.layout is not None:
            from repro.shard.round import (
                make_fleet_sharded_step, make_sharded_dynamic_flat_train_step)
            if mesh is not None and "model" in mesh.axis_names:
                return make_fleet_sharded_step(cfg, self.proto, spec, mesh,
                                               replicate_axis=axis,
                                               remat=remat)
            base = make_sharded_dynamic_flat_train_step(
                cfg, self.proto, spec, mesh=None, remat=remat)
        else:
            if unravel_row is None and spec is not None:
                unravel_row = spec.unravel_row
            if unravel_row is None:
                raise ValueError("the fleet step needs the unravel_row from "
                                 "init_flat_params (or a spec from "
                                 "init_flat_spec)")
            base = protocol_lib.make_dynamic_flat_train_step(
                cfg, self.proto, unravel_row)
        batched = jax.vmap(base)
        if mesh is None:
            return batched
        from jax.sharding import PartitionSpec
        n_dev = int(np.prod(mesh.devices.shape))
        if self.replicates % n_dev:
            raise ValueError(f"replicates={self.replicates} not divisible by "
                             f"mesh size {n_dev}")
        spec = PartitionSpec(mesh.axis_names[0])
        return jax.shard_map(batched, mesh=mesh,
                             in_specs=(spec, spec, spec, spec, spec),
                             out_specs=(spec, spec), check_vma=False)

    def make_fleet_round(self, cfg, mesh=None, unravel_row=None, spec=None):
        """Network advance + train step as ONE jittable call (what the
        sweep driver runs):

            fleet_round(key, states, flat, batch)
                -> (states', flat', metrics, chans, Ws)

        A single dispatch per round for the whole fleet — the unit the
        ≥3×-vs-Python-loop acceptance benchmark times. ``flat`` is the
        persistent [R, W, d] buffer (init_flat_params / init_flat_spec);
        with a model-sharded ``spec`` the buffer columns shard too (see
        make_fleet_step).
        """
        step = self.make_fleet_step(cfg, mesh=mesh, unravel_row=unravel_row,
                                    spec=spec)

        def fleet_round(key, states, flat, batch):
            k_net, k_step = jax.random.split(key)
            states, chans, _masks, Ws = self.round(k_net, states)
            flat, metrics = step(flat, batch, self.split_keys(k_step),
                                 chans, Ws)
            return states, flat, metrics, chans, Ws

        return fleet_round


def fleet_round_telemetry(proto, chans, Ws=None, spec=None) -> dict:
    """Host-side recompute of the channel telemetry columns over a stacked
    fleet log: ``chans``/``Ws`` leaves are [R, T, ...] (stack_rounds or a
    trajectory's out) and the result is {name: [R, T]} for every enabled
    channel scalar (+ per-round ε when the spec keeps it). This is the
    REFERENCE the in-scan fleet telemetry is tested against
    (tests/test_trajectory.py) — same formulas, recomputed from the logged
    channel states instead of inside the compiled chunk."""
    from repro.obs import telemetry as tele_lib
    spec = spec if spec is not None else tele_lib.TelemetrySpec()

    def one(ch, w):
        vals = tele_lib.channel_scalars(spec, ch, w)
        if spec.epsilon:
            vals["epsilon"] = tele_lib.epsilon_round(proto, ch, w)
        return vals

    if Ws is None:
        fn = jax.vmap(jax.vmap(lambda ch: one(ch, None)))
        return fn(chans)
    return jax.vmap(jax.vmap(one))(chans, Ws)


def fleet_epsilon_report(proto, chans, Ws=None) -> dict:
    """Replicated privacy report: Theorem 4.1 on every round of every
    replicate ([R, T, N] via the batched accounting — no Python loop),
    worst receiver per round, heterogeneous composition per replicate, and
    across-replicate mean/CI of the composed budget. ``chans`` leaves are
    [R, T, ...] (FleetEngine.trajectory or stack_rounds of logged rounds)."""
    from repro.core import accounting, privacy
    eps_rtn = np.asarray(privacy.epsilon_trajectory_batched(
        proto.gamma, proto.clip, chans, proto.delta, Ws))      # [R, T, N]
    per_round = eps_rtn.max(axis=2)                            # [R, T]
    eps_c, delta_c = privacy.compose_heterogeneous_batched(
        per_round, proto.delta)                                # [R], [R]
    mean, ci = mean_ci(eps_c)
    # both accountants per replicate at the SAME total δ budget
    # (δ-split rule; core.accounting) — epsilon_total is min(rdp,
    # advanced), the quote the fleet reports lead with
    both = accounting.compose_trajectory(per_round, proto.delta,
                                         delta_ref=proto.delta)
    adv_mean, adv_ci = mean_ci(both["epsilon_advanced"])
    rdp_mean, rdp_ci = mean_ci(both["epsilon_rdp"])
    tot_mean, tot_ci = mean_ci(both["epsilon"])
    return {
        "replicates": int(eps_rtn.shape[0]),
        "rounds": int(eps_rtn.shape[1]),
        "epsilon_per_round": per_round,                        # [R, T]
        "epsilon_worst": float(per_round.max()),
        "epsilon_composed_per_replicate": eps_c,               # [R]
        "delta_composed": float(delta_c.reshape(-1)[0]),
        "epsilon_composed_mean": mean,
        "epsilon_composed_ci95": ci,
        "epsilon_advanced_per_replicate": both["epsilon_advanced"],  # [R]
        "epsilon_rdp_per_replicate": both["epsilon_rdp"],      # [R]
        "epsilon_total_per_replicate": both["epsilon"],        # [R]
        "epsilon_advanced_mean": adv_mean,
        "epsilon_advanced_ci95": adv_ci,
        "epsilon_rdp_mean": rdp_mean,
        "epsilon_rdp_ci95": rdp_ci,
        "epsilon_total_mean": tot_mean,
        "epsilon_total_ci95": tot_ci,
        "accountant_gap": float(np.mean(both["gap_ratio"])),
        "delta_total": float(both["delta"]),
        "accountant": proto.accountant,
        "saturated": bool(np.any(both["saturated"])),
    }
