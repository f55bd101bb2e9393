"""ScenarioGrid — cartesian sweep driver over the fleet engine.

The paper's experiments are all sweeps (Figs. 2-6: worker count, transmit
power, privacy budget); this module is the systemized form. A grid point is
one (scenario, N, p_dbm, target_epsilon) cell; each cell runs R replicates
THROUGH ONE COMPILED PROGRAM (FleetEngine — the replicate axis carries the
seeds), trains the reduced benchmark task, and reports across-replicate
mean ± 95% CI for loss/accuracy and the composed privacy budget. Results
aggregate into a JSON document (``run_grid(..., json_path=...)``) so sweep
outputs are diffable artifacts, not printouts.

Cells with equal (scenario, N) share shapes; only p_dbm/ε differ — those
axes could additionally fold into the replicate axis via
``FleetEngine(power_dbm=[...])`` (power) when per-cell CI is not needed.
The driver keeps cells separate so every cell gets its own CI.

    PYTHONPATH=src python -m repro.fleet.sweep --steps 40 --replicates 8 \
        --json /tmp/fleet_sweep.json
"""
from __future__ import annotations

import argparse
import hashlib
import itertools
import json
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import protocol as P
from repro.core.exchange import make_flat_spec
from repro.fleet.engine import FleetEngine, fleet_epsilon_report, mean_ci, stack_rounds

# reduced benchmark task (mirrors benchmarks/common.py at smaller scale so a
# full grid stays interactive on one CPU core)
INPUT_DIM = 64
HIDDEN = 32
BATCH = 16
DATA_N = 2000


@dataclass(frozen=True)
class ScenarioGrid:
    """Cartesian product of scenario presets × worker counts × transmit
    powers × per-round privacy targets, each cell replicated R ways."""
    scenarios: Tuple[str, ...] = ("static_paper", "iot_dense")
    n_workers: Tuple[int, ...] = (8,)
    p_dbm: Tuple[float, ...] = (60.0,)
    target_epsilon: Tuple[float, ...] = (1.0,)
    replicates: int = 8
    steps: int = 40
    gamma: float = 0.02
    eta: float = 0.4
    clip: float = 1.0
    coherence_rounds: int = 0
    seed: int = 0
    # privacy ledger used for the quoted per-cell budget ("composition" |
    # "rdp"); the fleet report computes both, rows carry both plus the gap
    accountant: str = "composition"

    def points(self):
        for scn, n, p, eps in itertools.product(
                self.scenarios, self.n_workers, self.p_dbm,
                self.target_epsilon):
            yield {"scenario": scn, "n_workers": n, "p_dbm": p,
                   "target_epsilon": eps}

    def size(self) -> int:
        return (len(self.scenarios) * len(self.n_workers) * len(self.p_dbm)
                * len(self.target_epsilon))


def cell_seed(base_seed: int, point: Dict) -> int:
    """Deterministic per-cell seed: a stable hash of (base seed, cell
    settings). Every cell gets an INDEPENDENT PRNG stream — reusing the
    grid seed verbatim made all cells share their data shuffles and
    channel draws (correlated sampling error across the sweep) — yet the
    seed is reproducible from the row alone and independent of cell
    ORDER, so re-running a single cell reproduces its sweep result."""
    blob = json.dumps({"seed": base_seed, **point}, sort_keys=True,
                      default=str)
    return int.from_bytes(hashlib.sha256(blob.encode()).digest()[:4],
                          "big") % (2 ** 31)


def _setup_fleet_task(fleet: FleetEngine, seed: int):
    """Reduced classification task, replicated: R independent batch streams
    (different shuffle seeds — replicates must be i.i.d. through data order
    too) over the SAME underlying dataset/partition, stacked to
    [R, W, B, ...] per round."""
    from repro.configs.registry import get_arch
    from repro.data import (FederatedBatcher, classification_dataset,
                            dirichlet_partition)
    import repro.models.mlp as mlp

    proto = fleet.proto
    cfg = get_arch("dwfl-paper").replace(d_model=HIDDEN)
    x, y = classification_dataset(DATA_N, input_dim=INPUT_DIM, seed=seed)
    parts = dirichlet_partition(y, proto.n_workers, alpha=0.5, seed=seed)
    batchers = [FederatedBatcher(x, y, parts, batch_size=BATCH, seed=seed + r)
                for r in range(fleet.replicates)]

    def next_batch():
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[b.next() for b in batchers])

    def full_batch(n):
        one = batchers[0].full(n)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (fleet.replicates,) + a.shape),
            one)

    def init_fleet_params(key):
        """[R, W, ...]: per-replicate independent common-start init (the
        benchmark MLP takes input_dim, so the generic
        FleetEngine.init_worker_params config-default path does not apply)."""
        def one(k):
            p = mlp.init(k, cfg, input_dim=INPUT_DIM)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a[None], (proto.n_workers,) + a.shape), p)
        return jax.vmap(one)(fleet.split_keys(key))

    return cfg, next_batch, full_batch, init_fleet_params


def run_point(grid: ScenarioGrid, point: Dict, seed: int = 0,
              transfer_guard: bool = True) -> Dict:
    """One grid cell: R replicates batched through one compiled fleet round.
    Returns the cell's row — settings, the cell's own seed, the RESOLVED
    protocol + scenario configuration (so a row is re-runnable without the
    grid object), and across-replicate aggregates. ``transfer_guard``
    runs the timed loop under ``obs.no_implicit_transfers`` — the cell
    timing is the sweep's PRODUCT, so an implicit per-round host transfer
    silently corrupting ``us_per_round`` must fail loudly instead."""
    proto = P.ProtocolConfig(
        scheme="dwfl", n_workers=point["n_workers"], gamma=grid.gamma,
        eta=grid.eta, clip=grid.clip, p_dbm=point["p_dbm"], seed=seed,
        target_epsilon=point["target_epsilon"], channel_model="dynamic",
        scenario=point["scenario"], coherence_rounds=grid.coherence_rounds,
        replicates=grid.replicates, accountant=grid.accountant)
    fleet = FleetEngine(proto)
    cfg, next_batch, full_batch, init_params = _setup_fleet_task(fleet, seed)

    evaluate = jax.vmap(P.make_eval_fn(cfg))

    key = jax.random.PRNGKey(seed)
    key, k_net, k_wp = jax.random.split(key, 3)
    states = fleet.init(k_net)
    tree = init_params(k_wp)
    spec = make_flat_spec(tree, lead_axes=2)
    wp = spec.flatten(tree)
    fleet_round = jax.jit(fleet.make_fleet_round(cfg, spec=spec),
                          donate_argnums=(1, 2))

    chan_log, w_log = [], []
    # warmup/compile outside the timed region
    key, rk = jax.random.split(key)
    states, wp, metrics, chans, Ws = fleet_round(rk, states, wp, next_batch())
    chan_log.append(chans)
    w_log.append(Ws)
    t0 = time.perf_counter()
    for _ in range(grid.steps):
        key, rk = jax.random.split(key)
        # batch assembly (host NumPy -> device stack) stays OUTSIDE the
        # guard; the guarded dispatch must touch device data only
        batch = next_batch()
        with obs.no_implicit_transfers(transfer_guard):
            states, wp, metrics, chans, Ws = fleet_round(rk, states, wp,
                                                         batch)
        chan_log.append(chans)
        w_log.append(Ws)
    jax.tree_util.tree_leaves(wp)[0].block_until_ready()
    us_per_round = (time.perf_counter() - t0) / grid.steps * 1e6

    ev_loss, ev_acc = evaluate(spec.unravel(wp), full_batch(128))  # [R]
    eps_rep = fleet_epsilon_report(
        proto, stack_rounds(chan_log), stack_rounds(w_log))

    loss_mean, loss_ci = mean_ci(np.asarray(ev_loss))
    acc_mean, acc_ci = mean_ci(np.asarray(ev_acc))
    return {
        **point,
        "seed": seed,
        "replicates": grid.replicates,
        "steps": grid.steps,
        "config": {"protocol": asdict(proto),
                   "scenario": asdict(fleet.sim.scenario)},
        "us_per_round": us_per_round,
        "loss_mean": loss_mean, "loss_ci95": loss_ci,
        "acc_mean": acc_mean, "acc_ci95": acc_ci,
        "epsilon_composed_mean": eps_rep["epsilon_composed_mean"],
        "epsilon_composed_ci95": eps_rep["epsilon_composed_ci95"],
        "epsilon_round_worst": eps_rep["epsilon_worst"],
        "delta_composed": eps_rep["delta_composed"],
        "epsilon_rdp_mean": eps_rep["epsilon_rdp_mean"],
        "epsilon_total_mean": eps_rep["epsilon_total_mean"],
        "epsilon_total_ci95": eps_rep["epsilon_total_ci95"],
        "delta_total": eps_rep["delta_total"],
        "accountant": grid.accountant,
        "accountant_gap": eps_rep["accountant_gap"],
    }


def run_grid(grid: ScenarioGrid, seed: Optional[int] = None,
             json_path: Optional[str] = None, verbose: bool = False,
             runlog: Optional[obs.RunLog] = None,
             transfer_guard: bool = True) -> Dict:
    """Sweep every cell; returns {"grid": settings, "rows": [cell rows]}
    and optionally writes it as JSON. Each cell runs under its OWN
    derived seed (``cell_seed(base, point)``); ``runlog`` (repro.obs)
    records one "cell" event per completed row."""
    base = grid.seed if seed is None else seed
    rows: List[Dict] = []
    for point in grid.points():
        row = run_point(grid, point, seed=cell_seed(base, point),
                        transfer_guard=transfer_guard)
        rows.append(row)
        if runlog is not None:
            runlog.event("cell", **{k: v for k, v in row.items()
                                    if k != "config"})
        if verbose:
            obs.console(
                f"[sweep] {row['scenario']} N={row['n_workers']} "
                f"P={row['p_dbm']}dBm eps={row['target_epsilon']} "
                f"seed={row['seed']}: "
                f"acc={row['acc_mean']:.3f}±{row['acc_ci95']:.3f} "
                f"eps_T={row['epsilon_composed_mean']:.3g}"
                f"±{row['epsilon_composed_ci95']:.2g} "
                f"rdp={row['epsilon_rdp_mean']:.3g} "
                f"({row['us_per_round']:.0f}us/round x R={row['replicates']})")
    out = {"grid": asdict(grid), "rows": rows}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        if verbose:
            obs.console(f"[sweep] wrote {len(rows)} cells -> {json_path}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", default="static_paper,iot_dense")
    ap.add_argument("--workers", default="8")
    ap.add_argument("--p-dbm", default="60")
    ap.add_argument("--epsilon", default="1.0")
    ap.add_argument("--replicates", type=int, default=8)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--accountant", default="composition",
                    choices=["composition", "rdp"],
                    help="ledger quoted per cell (rows always carry "
                         "both composed and rdp budgets + the gap)")
    ap.add_argument("--no-transfer-guard", action="store_true",
                    help="disable jax.transfer_guard('disallow') around "
                         "the timed per-cell loops")
    ap.add_argument("--json", default=None)
    ap.add_argument("--runlog-dir", default=None,
                    help="open a structured run log under this directory "
                         "(repro.obs: one 'cell' event per grid cell)")
    args = ap.parse_args(argv)
    grid = ScenarioGrid(
        scenarios=tuple(args.scenarios.split(",")),
        n_workers=tuple(int(v) for v in args.workers.split(",")),
        p_dbm=tuple(float(v) for v in args.p_dbm.split(",")),
        target_epsilon=tuple(float(v) for v in args.epsilon.split(",")),
        replicates=args.replicates, steps=args.steps, seed=args.seed,
        accountant=args.accountant)
    runlog = None
    if args.runlog_dir is not None:
        runlog = obs.RunLog.open_under(args.runlog_dir, kind="sweep",
                                       config=asdict(grid), seed=args.seed,
                                       argv=argv)
        obs.console(f"[sweep] run log -> {runlog.dir}")
    run_grid(grid, json_path=args.json, verbose=True, runlog=runlog,
             transfer_guard=not args.no_transfer_guard)
    if runlog is not None:
        runlog.close("ok", cells=grid.size())


if __name__ == "__main__":
    main()
