"""Trajectory-engine perf tracking: K-round scan chunks vs the per-round
dispatch loop, written to ``BENCH_trajectory.json`` at the repo root so the
perf trajectory is versioned alongside the code.

    PYTHONPATH=src python -m benchmarks.trajectory_bench [--smoke]

Three cases, one per driver path — static channel, dynamic (repro.net),
fleet (R=8 replicates) — each timing rounds/sec of

  * per-round: a one-dispatch-per-round driver loop (host
    ``jax.random.split`` + NumPy batch assembly + one jitted dispatch per
    round + per-round chan/W list appends), vs
  * scan: ``ChunkRunner.run`` — one dispatch per K-round ``lax.scan``
    chunk with on-device batch sampling (repro.data.device).

All cases run the flat-buffer round (the fused dp_mix path) — the
repo's only layout, and the regime the scan engine exists for: once the O(d)
round body is one fused kernel, per-round dispatch + host work dominate
wall-clock (ISSUE 4 / the edge-mesh bottleneck of PAPERS.md). Task scale
follows the benchmarks.common convention (the paper MLP config at smoke
width) so the suite runs on one CPU core; the comparisons, not absolute
rates, are the artifact. The full run ASSERTS the >= 2x acceptance
speedup at K >= 32 on every path.

CSV rows (benchmarks.run convention): derived = scan-over-per-round
rounds/sec speedup. The JSON carries both rates per case plus the shape.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_trajectory.json"
# the CI --smoke gate writes its tiny-shape numbers into the gitignored
# bench_out/ scratch directory so they never land at the repo root next to
# (or get committed alongside) the versioned full-run artifact above
OUT_SMOKE = ROOT / "bench_out" / "BENCH_trajectory_smoke.json"

# the paper MLP config at smoke width (dispatch-dominated regime: the
# fused flat-buffer round is O(100us), so per-round host work is the
# bottleneck the scan removes). W = 8 matches the dp_mix sublane tile.
INPUT_DIM = 32
HIDDEN = 8
DATA_N = 2000
N_WORKERS = 8
BATCH = 2
R_FLEET = 8
CHUNK = 32          # the acceptance K
SPEEDUP_FLOOR = 2.0


def _task(n_workers: int, batch: int, seed: int = 0):
    from repro.configs.registry import get_arch
    from repro.core import exchange as X
    from repro.data import (FederatedBatcher, classification_dataset,
                            dirichlet_partition, store_from_batcher)
    import repro.models.mlp as mlp

    cfg = get_arch("dwfl-paper").replace(d_model=HIDDEN)
    x, y = classification_dataset(DATA_N, input_dim=INPUT_DIM, seed=seed)
    parts = dirichlet_partition(y, n_workers, alpha=0.5, seed=seed)
    bat = FederatedBatcher(x, y, parts, batch, seed=seed)
    store = store_from_batcher(bat)
    params = mlp.init(jax.random.PRNGKey(seed), cfg, input_dim=INPUT_DIM)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_workers,) + a.shape), params)
    spec = X.make_flat_spec(wp)
    return cfg, bat, store, spec.flatten(wp), spec.unravel_row


def _rate_pair(run_a, run_b, total_rounds: int, passes: int = 3,
               min_pass_s: float = 0.4):
    """(rounds/sec of run_a, of run_b): passes are INTERLEAVED a/b/a/b so
    machine-load drift on a shared CPU biases both sides equally, and each
    timed pass repeats its runner until >= min_pass_s so scheduler noise
    averages out; best pass each, after a warmup/compile pass each."""
    def reps_for(run):
        jax.block_until_ready(run())           # warmup/compile
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        once = max(time.perf_counter() - t0, 1e-6)
        return max(1, int(min_pass_s / once) + 1)

    def timed(run, reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(run())
        return (time.perf_counter() - t0) / reps

    reps_a, reps_b = reps_for(run_a), reps_for(run_b)
    best_a = best_b = float("inf")
    for _ in range(passes):
        best_a = min(best_a, timed(run_a, reps_a))
        best_b = min(best_b, timed(run_b, reps_b))
    return total_rounds / best_a, total_rounds / best_b


def _scan_runner(body, carry0, k: int, chunks: int):
    from repro.core import trajectory as TJ
    runner = TJ.ChunkRunner(body, donate=False)

    def run_T():
        c = carry0
        for _ in range(chunks):
            c, _out = runner.run(c, k)
        return c.params

    return run_T


def _case(path: str, k: int, chunks: int, n_workers: int, batch: int,
          replicates: int = 1) -> dict:
    """One (path, K) case: rounds/sec of the legacy per-round loop vs the
    K-chunked scan, identical flat-buffer task and protocol."""
    from repro.core import protocol as P
    from repro.core import trajectory as TJ

    cfg, bat, store, flat, unravel_row = _task(n_workers, batch)
    T = k * chunks
    key = jax.random.PRNGKey(1)

    if path == "static":
        proto = P.ProtocolConfig(scheme="dwfl", n_workers=n_workers,
                                 p_dbm=60.0, sigma=0.7)
        step = jax.jit(P.make_flat_train_step(cfg, proto, unravel_row))

        def per_round():
            kk, f = key, flat
            for _ in range(T):
                kk, sk = jax.random.split(kk)
                f, _m = step(f, bat.next(), sk)
            return f

        body = TJ.make_round_body(cfg, proto, store,
                                  unravel_row=unravel_row)
        carry0 = TJ.TrajCarry(key, flat)
    elif path == "dynamic":
        proto = P.ProtocolConfig(scheme="dwfl", n_workers=n_workers,
                                 p_dbm=60.0, channel_model="dynamic",
                                 scenario="iot_dense")
        sim = proto.simulator()
        net0 = sim.init(jax.random.PRNGKey(2))
        step = jax.jit(P.make_dynamic_flat_train_step(cfg, proto,
                                                      unravel_row))
        net_round = jax.jit(sim.round)

        def per_round():
            kk, f, ns = key, flat, net0
            chan_log, w_log = [], []
            for _ in range(T):
                kk, sk = jax.random.split(kk)
                sk, ck = jax.random.split(sk)
                ns, chan, _mask, Wt = net_round(ck, ns)
                chan_log.append(chan)
                w_log.append(Wt)
                f, _m = step(f, bat.next(), sk, chan, Wt)
            return f

        body = TJ.make_round_body(cfg, proto, store, sim=sim,
                                  unravel_row=unravel_row)
        carry0 = TJ.TrajCarry(key, flat, net0)
    elif path == "fleet":
        from repro.fleet import FleetEngine
        proto = P.ProtocolConfig(scheme="dwfl", n_workers=n_workers,
                                 p_dbm=60.0, channel_model="dynamic",
                                 scenario="iot_dense", replicates=replicates)
        fleet = FleetEngine(proto)
        net0 = fleet.init(jax.random.PRNGKey(2))
        flatR = jnp.broadcast_to(flat[None], (replicates,) + flat.shape) + 0.0
        fleet_round = jax.jit(fleet.make_fleet_round(
            cfg, unravel_row=unravel_row))

        def next_batch():
            # the R-fold host stacking the device store replaces
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[bat.next() for _ in range(replicates)])

        def per_round():
            kk, f, ns = key, flatR, net0
            chan_log, w_log = [], []
            for _ in range(T):
                kk, sk = jax.random.split(kk)
                ns, f, _m, chan, Wt = fleet_round(sk, ns, f, next_batch())
                chan_log.append(chan)
                w_log.append(Wt)
            return f

        body = TJ.make_round_body(cfg, proto, store, fleet=fleet,
                                  unravel_row=unravel_row)
        carry0 = TJ.TrajCarry(key, flatR, net0)
    else:
        raise ValueError(path)

    rps_loop, rps_scan = _rate_pair(per_round,
                                    _scan_runner(body, carry0, k, chunks), T)
    return {"path": path, "chunk": k, "rounds": T,
            "workers": n_workers, "batch": batch,
            "replicates": replicates if path == "fleet" else 1,
            "per_round_rps": round(rps_loop, 2),
            "scan_rps": round(rps_scan, 2),
            "scan_us_per_round": round(1e6 / rps_scan, 1),
            "speedup": round(rps_scan / rps_loop, 3)}


def smoke_case() -> dict:
    """The kernel-bench/CI acceptance case: static path, K=32 — the fused
    round is dispatch-dominated, so the scan win must be unambiguous."""
    return _case("static", k=CHUNK, chunks=4, n_workers=N_WORKERS,
                 batch=BATCH)


def main(steps: int = 250, smoke: bool = False):
    chunks = 2 if smoke else max(3, min(steps // CHUNK, 6))
    cases = [
        _case("static", CHUNK, chunks, N_WORKERS, BATCH),
        _case("dynamic", CHUNK, chunks, N_WORKERS, BATCH),
        _case("fleet", CHUNK, chunks, N_WORKERS, BATCH,
              replicates=R_FLEET),
    ]
    from benchmarks.common import provenance
    report = {
        "benchmark": "trajectory_scan_vs_per_round",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "provenance": provenance(smoke),
        "chunk_rounds": CHUNK,
        "flat_buffer": True,
        "speedup_floor": SPEEDUP_FLOOR,
        "cases": cases,
    }
    out = OUT_SMOKE if smoke else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    if not smoke:
        # the ISSUE-4 acceptance gate: >= 2x rounds/sec at K >= 32 on
        # every driver path (the smoke gate in ci_check.sh asserts its own
        # looser floor on the shorter run)
        for c in cases:
            assert c["speedup"] >= SPEEDUP_FLOOR, (
                f"{c['path']}: scan only {c['speedup']:.2f}x vs per-round "
                f"dispatch at K={CHUNK} (need >= {SPEEDUP_FLOOR}x)")
    rows = [f"trajectory/{c['path']}_k{c['chunk']},"
            f"{c['scan_us_per_round']:.1f},{c['speedup']:.2f}"
            for c in cases]
    rows.append(f"trajectory/report,{0.0:.1f},{str(out.name)}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short run, fast (CI gate)")
    ap.add_argument("--steps", type=int, default=250)
    args = ap.parse_args()
    print("\n".join(main(args.steps, smoke=args.smoke)))
