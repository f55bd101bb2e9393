"""Fleet acceptance benchmark: batched replicates vs the Python loop.

Case 1 (``fleet/batch_vs_loop_R8``): advance R=8 independent iot_dense
networks for the same number of rounds two ways —

  loop   the pre-fleet pattern: per-replicate jitted net_round + train
         step, iterated in Python (2R dispatches/round),
  fleet  ONE jitted fleet_round vmapped over the stacked [R, ...] state
         (1 dispatch/round, XLA fuses the R-way small ops).

Identical compute per round; derived = loop/fleet wall-clock ratio after
warmup, asserted >= 3x (the ISSUE 2 acceptance bar). Both paths consume a
fixed preallocated batch — the benchmark times the simulation engine, not
the data pipeline.

Case 2 (``fleet/grid_2cells``): a tiny ScenarioGrid sweep end-to-end
(mean/CI JSON aggregation); derived = across-replicate mean accuracy.
"""
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import protocol as P
from repro.core.exchange import make_flat_spec
from repro.fleet import FleetEngine
from repro.fleet.sweep import ScenarioGrid, run_grid

R = 8
N_WORKERS = 4      # small per-replicate compute: the loop's 2R-dispatch
STEPS = 30         # overhead is the bottleneck being measured
INPUT_DIM = 32
HIDDEN = 16
BATCH = 8

MIN_SPEEDUP = 3.0


def _tiny_setup():
    from repro.configs.registry import get_arch
    import repro.models.mlp as mlp
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=N_WORKERS, gamma=0.02,
                             eta=0.4, clip=1.0, p_dbm=60.0,
                             target_epsilon=1.0, channel_model="dynamic",
                             scenario="iot_dense", replicates=R)
    cfg = get_arch("dwfl-paper").replace(d_model=HIDDEN)
    key = jax.random.PRNGKey(0)
    params = mlp.init(key, cfg, input_dim=INPUT_DIM)
    wp1 = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (N_WORKERS,) + a.shape), params)
    batch1 = {"x": jax.random.normal(key, (N_WORKERS, BATCH, INPUT_DIM)),
              "y": jnp.zeros((N_WORKERS, BATCH), jnp.int32)}
    return proto, cfg, wp1, batch1


def _flat(tree, lead_axes=1):
    """(flat buffer, FlatSpec) of a worker-stacked tree."""
    spec = make_flat_spec(tree, lead_axes=lead_axes)
    return spec.flatten(tree), spec


def bench_batch_vs_loop(steps: int = STEPS):
    proto, cfg, wp1, batch1 = _tiny_setup()
    key = jax.random.PRNGKey(1)

    # -- loop path: R independent single-replicate pipelines ---------------
    sim = proto.simulator()
    net_round = jax.jit(sim.round)
    flat1, spec1 = _flat(wp1)
    step = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                  spec1.unravel_row))
    loop_states = [sim.init(jax.random.fold_in(key, r)) for r in range(R)]
    loop_wp = [flat1 for _ in range(R)]

    def loop_round(t):
        for r in range(R):
            k = jax.random.fold_in(jax.random.fold_in(key, t), r)
            k_net, k_step = jax.random.split(k)
            loop_states[r], chan, _mask, Wm = net_round(k_net, loop_states[r])
            loop_wp[r], _ = step(loop_wp[r], batch1, k_step, chan, Wm)

    loop_round(0)  # warmup/compile
    t0 = time.perf_counter()
    for t in range(steps):
        loop_round(t + 1)
    loop_wp[-1].block_until_ready()
    loop_us = (time.perf_counter() - t0) / steps * 1e6

    # -- fleet path: same R networks through one compiled round ------------
    fleet = FleetEngine(proto)
    wp, specR = _flat(jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), wp1), 2)
    fleet_round = jax.jit(fleet.make_fleet_round(cfg, spec=specR))
    states = fleet.init(key)
    batch = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), batch1)

    states, wp, metrics, _c, _w = fleet_round(key, states, wp, batch)  # warmup
    t0 = time.perf_counter()
    for t in range(steps):
        states, wp, metrics, _c, _w = fleet_round(
            jax.random.fold_in(key, t), states, wp, batch)
    wp.block_until_ready()
    fleet_us = (time.perf_counter() - t0) / steps * 1e6

    speedup = loop_us / fleet_us
    return fleet_us, loop_us, speedup


def bench_grid():
    grid = ScenarioGrid(scenarios=("static_paper", "iot_dense"),
                        n_workers=(6,), p_dbm=(60.0,), target_epsilon=(1.0,),
                        replicates=4, steps=10)
    path = os.path.join(tempfile.mkdtemp(prefix="fleet_sweep_"),
                        "sweep.json")
    t0 = time.perf_counter()
    out = run_grid(grid, json_path=path)
    us = (time.perf_counter() - t0) * 1e6
    with open(path) as f:
        rows = json.load(f)["rows"]
    assert len(rows) == grid.size() and all("acc_ci95" in r for r in rows)
    acc = float(np.mean([r["acc_mean"] for r in rows]))
    return us, acc


def main(steps: int = STEPS):
    rows = []
    # timing iterations, not training steps: clamp up so a small --steps
    # doesn't turn the >=3x acceptance assert into timing noise
    fleet_us, loop_us, speedup = bench_batch_vs_loop(max(steps, 20))
    rows.append(f"fleet/batch_vs_loop_R{R},{fleet_us:.1f},{speedup:.2f}")
    assert speedup >= MIN_SPEEDUP, (
        f"fleet batched round only {speedup:.2f}x faster than the "
        f"R-iteration Python loop (acceptance bar: >={MIN_SPEEDUP}x); "
        f"loop={loop_us:.0f}us fleet={fleet_us:.0f}us")
    us, acc = bench_grid()
    rows.append(f"fleet/grid_2cells,{us:.1f},{acc:.4f}")
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
