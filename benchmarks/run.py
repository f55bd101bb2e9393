"""Benchmark aggregator: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV. ``derived`` is final eval accuracy
for the training figures, the privacy-amplification ratio for the analytic
table, and max-abs-error for the kernel benches.

    PYTHONPATH=src python -m benchmarks.run [--steps 250] [--only fig5]
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=250)
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module names")
    args = ap.parse_args()

    from benchmarks import (fig2_power, fig3_workers, fig4_epsilon,
                            fig5_orthogonal, fig6_centralized,
                            privacy_table, kernel_bench, sampling_ablation,
                            accounting_bench, coherence_sweep,
                            fleet_sweep, trajectory_bench,
                            workers_bench)

    suites = [
        ("fig2_power", lambda: fig2_power.main(args.steps)),
        ("fig3_workers", lambda: fig3_workers.main(args.steps)),
        ("fig4_epsilon", lambda: fig4_epsilon.main(args.steps)),
        ("fig5_orthogonal", lambda: fig5_orthogonal.main(args.steps)),
        ("fig6_centralized", lambda: fig6_centralized.main(args.steps)),
        ("privacy_table", privacy_table.main),
        ("kernel_bench", kernel_bench.main),
        # emits BENCH_trajectory.json at the repo root (K-chunked scan vs
        # per-round dispatch rounds/sec; asserts the >= 2x acceptance)
        ("trajectory_bench", lambda: trajectory_bench.main(args.steps)),
        # emits BENCH_workers.json at the repo root (dense vs sparse
        # dp_mix round over N in 64..8192; asserts the >= 3x acceptance
        # at N >= 2048 and sub-quadratic sparse peak-memory growth)
        ("workers_bench", workers_bench.main),
        # emits BENCH_accounting.json at the repo root (RDP vs advanced-
        # composition ε gap and matched-ε σ saving over T in 32..1024;
        # asserts the >= 15% acceptance at T = 512)
        ("accounting_bench", accounting_bench.main),
        ("sampling_ablation", lambda: sampling_ablation.main(args.steps)),
        ("fleet_sweep", lambda: fleet_sweep.main(args.steps)),
        ("coherence_sweep", lambda: coherence_sweep.main(args.steps)),
    ]
    print("name,us_per_call,derived")
    ok = True
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            for r in fn():
                print(r, flush=True)
        except Exception as e:  # noqa: BLE001
            ok = False
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
