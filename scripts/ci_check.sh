#!/usr/bin/env bash
# CI gate: the tier-1 test command (ROADMAP.md) plus a bounded repro.net
# dynamic-scenario smoke run (~2 minutes on one CPU core).
#
#   ./scripts/ci_check.sh            # full tier-1 + smoke
#   ./scripts/ci_check.sh --fast     # fast test tier (-m "not claims",
#                                    # pytest-xdist when available) + smoke
#   ./scripts/ci_check.sh --smoke    # smoke only (fast sanity)
#   ./scripts/ci_check.sh --lint     # repro.analysis static lint: jaxpr/HLO
#                                    # checkers over the compiled program
#                                    # registry + AST source lint; writes
#                                    # bench_out/analysis_report.json
#
# The statistical claims tier (tests/test_claims.py, -m claims) runs in
# its own CI job; the full (default) mode here includes it.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# the lint tier is self-contained: build + statically check the shipped
# compiled programs (repro.analysis), fail on any ERROR-severity finding,
# and leave the JSON report behind as the CI artifact
if [[ "${1:-}" == "--lint" ]]; then
    echo "== lint tier: repro.analysis (jaxpr/HLO checkers + source lint) =="
    mkdir -p bench_out
    # force a 4-device host platform so the registry's REAL mesh program
    # (shard-flat-s2-mesh — the gather-free checker's main target) builds
    # instead of dropping out of available_programs()
    XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m repro.analysis --json bench_out/analysis_report.json
    echo "ci_check --lint: OK"
    exit 0
fi

# pytest-xdist is a CI nicety, not a container guarantee
XDIST=""
if python -c "import xdist" >/dev/null 2>&1; then
    XDIST="-n auto"
fi

# the per-ISSUE regression pytest re-runs below duplicate the --fast/full
# tiers (both already collect those modules); only --smoke mode, which runs
# no pytest tier, still needs them
RUN_REGRESSION=0
if [[ "${1:-}" == "--smoke" ]]; then RUN_REGRESSION=1; fi

if [[ "${1:-}" == "--fast" ]]; then
    echo "== fast tier: pytest -m 'not claims' ${XDIST} =="
    python -m pytest -q -m "not claims" ${XDIST}
elif [[ "${1:-}" != "--smoke" ]]; then
    echo "== tier-1: pytest =="
    python -m pytest -x -q
fi

echo "== repro.net smoke: dynamic scenario, 40 rounds =="
python -m repro.launch.train \
    --arch dwfl-paper --steps 40 --workers 8 \
    --channel-model dynamic --scenario iot_dense --coherence-rounds 10 \
    --eval-every 20

echo "== repro.net smoke: zero-retrace kernel bench =="
python - <<'EOF'
from benchmarks.kernel_bench import _bench_net_retrace
row = _bench_net_retrace()
print(row)
name, us, traces = row.split(",")
assert float(traces) == 1.0, f"dynamic exchange retraced: {traces}"
EOF

echo "== repro.fleet smoke: R=4 replicates, one compiled step =="
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 \
    --channel-model dynamic --scenario iot_dense --replicates 4 \
    --eval-every 5

echo "== repro.fleet smoke: zero retraces across replicate batches =="
python - <<'EOF'
from benchmarks.kernel_bench import _bench_fleet_retrace
row = _bench_fleet_retrace()
print(row)
name, us, traces = row.split(",")
assert float(traces) == 1.0, f"fleet exchange retraced: {traces}"
EOF

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 2 regression tests: sampling amplification + scheme composition =="
python -m pytest -q \
    tests/test_dwfl.py::test_sampled_mask_no_fixed_subset \
    tests/test_dwfl.py::test_sampled_report_quotes_effective_rate \
    tests/test_dwfl.py::test_orthogonal_deep_fade_bounded \
    tests/test_privacy.py::test_epsilon_report_composes_scheme_budget \
    tests/test_fleet.py
fi

echo "== ISSUE 3 smoke: fused dp_mix round (>=1.5x + zero retraces) =="
python - <<'EOF'
from benchmarks.kernel_bench import _bench_dp_mix, _bench_dp_mix_retrace
row = _bench_dp_mix()              # asserts the >= 1.5x fusion speedup
print(row)
row = _bench_dp_mix_retrace()
print(row)
assert float(row.split(",")[2]) == 1.0, f"dp_mix retraced: {row}"
EOF

echo "== ISSUE 3 smoke: flat-buffer training path (static, fleet) =="
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --eval-every 5
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 \
    --channel-model dynamic --scenario iot_dense --replicates 2 \
    --eval-every 5

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 3 regression tests: unified exchange engine =="
python -m pytest -q tests/test_exchange.py \
    tests/test_dwfl.py::test_eval_fn_lm_next_token_accuracy
python -m pytest -q tests/test_kernels.py -k dp_mix
fi

echo "== ISSUE 4 smoke: scan-fused trajectory engine (>=2x vs per-round) =="
python - <<'EOF'
from benchmarks.kernel_bench import _bench_trajectory_scan
print(_bench_trajectory_scan())   # asserts the >= 2x scan speedup
EOF

echo "== ISSUE 4 smoke: trajectory perf artifact (smoke run) =="
python -m benchmarks.trajectory_bench --smoke
python - <<'EOF'
import json
# smoke writes into gitignored bench_out/ so it never clobbers (or gets
# committed next to) the versioned full-run BENCH_trajectory.json artifact
rep = json.load(open("bench_out/BENCH_trajectory_smoke.json"))
assert {c["path"] for c in rep["cases"]} == {"static", "dynamic", "fleet"}, rep
assert any(c["replicates"] == 8 for c in rep["cases"]), rep
for c in rep["cases"]:
    # shorter smoke run => looser floor than the full-run 2x acceptance
    assert c["speedup"] > 1.3, c
print("bench_out/BENCH_trajectory_smoke.json:",
      ", ".join(f"{c['path']}: {c['speedup']}x" for c in rep["cases"]))
EOF

echo "== ISSUE 4 smoke: chunked scan driver (static + dynamic fleet) =="
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --batch-size 8 \
    --chunk-rounds 4 --eval-every 5
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense --replicates 2 \
    --chunk-rounds 4 --eval-every 5

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 4 regression tests: scan-vs-loop equivalence =="
python -m pytest -q -m "not slow" tests/test_trajectory.py
fi

echo "== ISSUE 5 smoke: model-sharded flat buffer (repro.shard) =="
# logical sharding on one device, then a REAL model=2 host-device mesh
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --batch-size 8 \
    --model-shards 2 --chunk-rounds 4 --eval-every 5
XLA_FLAGS=--xla_force_host_platform_device_count=2 python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --batch-size 8 \
    --model-shards 2 --chunk-rounds 4 --eval-every 5

echo "== ISSUE 8 smoke: gather-free grad pass (chunk plan + remat) =="
XLA_FLAGS=--xla_force_host_platform_device_count=2 python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 5 --batch-size 8 \
    --model-shards 2 --max-chunk-cols 131072 --remat \
    --chunk-rounds 4 --eval-every 5

echo "== ISSUE 8 smoke: shard perf artifact (4 forced devices, S in 1/2/4) =="
# shard_bench forces a 4-device host platform itself and cross-checks
# every sharded case against the unsharded round (to float32 rounding)
# before timing anything
python -m benchmarks.shard_bench --smoke
python - <<'EOF'
import json
rep = json.load(open("bench_out/BENCH_shard_smoke.json"))
cases = {c["shards"]: c for c in rep["cases"]}
assert set(cases) == {1, 2, 4}, rep
# throughput sanity floor: at SMOKE shapes (hidden 64) the collectives
# dominate, so the bar is "not pathologically slow", not the full-size
# bench's >= 1.0x acceptance (BENCH_shard.json, hidden 512)
for S in (2, 4):
    assert cases[S]["speedup_vs_s1"] > 0.35, cases[S]
# the gather-free contract: compiled per-device peak shrinks with S
peaks = [cases[S]["peak_bytes_per_device"] for S in (1, 2, 4)]
assert None not in peaks and peaks[0] > peaks[1] > peaks[2], peaks
print("bench_out/BENCH_shard_smoke.json:",
      ", ".join(f"S={S}: {cases[S]['us_per_round']}us/round, "
                f"peak {cases[S]['peak_bytes_per_device']/1e6:.1f}MB"
                for S in (1, 2, 4)))
EOF

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 5 regression tests: shard parity + checkpoint roundtrip =="
python -m pytest -q -m "not slow" tests/test_shard.py tests/test_checkpoint.py
fi

echo "== ISSUE 7 lint: AST source lint (no stray print in library code) =="
# the PR 6 grep, promoted into repro.analysis: parses real print() CALLS
# (no string/pprint false hits) and shares the Finding schema + ERROR
# gate with the jaxpr checkers; the full jaxpr/HLO pass runs in the
# dedicated `--lint` tier / CI lint job
python -m repro.analysis --source-only

echo "== ISSUE 6 smoke: runlog-enabled train + report =="
# a fixed gitignored location so CI can upload the run log as an artifact
OBS_RUNDIR="bench_out/runlogs"
rm -rf "$OBS_RUNDIR" && mkdir -p "$OBS_RUNDIR"
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 6 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense \
    --chunk-rounds 4 --eval-every 5 --runlog-dir "$OBS_RUNDIR"
python -m repro.obs.report "$OBS_RUNDIR"/*
python - "$OBS_RUNDIR" <<'EOF'
import json, pathlib, sys
run = next(pathlib.Path(sys.argv[1]).iterdir())
man = json.loads((run / "manifest.json").read_text())
assert man["status"] == "ok", man
rounds = [json.loads(l) for l in (run / "events.jsonl").open()
          if json.loads(l)["type"] == "round"]
assert len(rounds) == 11 and all("epsilon" in r for r in rounds), \
    (len(rounds), rounds[:1])
print(f"{run.name}: {len(rounds)} round events, status=ok")
EOF

echo "== ISSUE 6 smoke: telemetry overhead artifact (smoke shapes) =="
python -m benchmarks.obs_bench --smoke
python - <<'EOF'
import json
rep = json.load(open("bench_out/BENCH_obs_smoke.json"))
assert {c["path"] for c in rep["cases"]} == {"static", "dynamic", "fleet"}
for c in rep["cases"]:
    assert c["guard_traces"] == 2, c   # one compile per runner, ever
print("bench_out/BENCH_obs_smoke.json:",
      ", ".join(f"{c['path']}: {c['overhead_frac']:+.1%}"
                for c in rep["cases"]))
EOF

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 6 regression tests: telemetry + runlog/watchdogs =="
python -m pytest -q -m "not slow" tests/test_obs.py
python -m pytest -q tests/test_trajectory.py -k "telemetry or consensus"
fi

echo "== ISSUE 9 smoke: sparse neighbor-list training path =="
# sparse mixing end to end (graph emission -> kernel -> eps) + the
# isolated-worker fallback, then the worker-axis row shard on a REAL
# 2-device mesh
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 8 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense --sparse-neighbors 3 \
    --chunk-rounds 4 --eval-every 5
python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 16 --batch-size 8 \
    --channel-model dynamic --scenario mesh_sparse --sparse-neighbors 4 \
    --graph-fallback --chunk-rounds 4 --eval-every 5
XLA_FLAGS=--xla_force_host_platform_device_count=2 python -m repro.launch.train \
    --arch dwfl-paper --steps 10 --workers 8 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense --sparse-neighbors 3 \
    --worker-shards 2 --chunk-rounds 4 --eval-every 0

echo "== ISSUE 9 smoke: worker-scale perf artifact (N in 128/256/512) =="
# cross-checks sparse vs the dense reference round before timing anything
python -m benchmarks.workers_bench --smoke
python - <<'EOF'
import json
rep = json.load(open("bench_out/BENCH_workers_smoke.json"))
cases = {c["n_workers"]: c for c in rep["cases"]}
assert set(cases) == {128, 256, 512}, rep
assert all(c["crosschecked"] for c in rep["cases"]), rep
# throughput gate: by N=512 the O(N*k*d) round must have overtaken the
# dense O(N^2*d) one (the full-run BENCH_workers.json asserts >= 3x at
# N >= 2048; the smoke bar is the crossover itself)
assert cases[512]["speedup"] >= 1.0, cases[512]
# memory gate: sub-quadratic sparse growth over the 4x N step (quadratic
# would be 16x) and strictly slower growth than the dense leg's
s128, s512 = (cases[128]["sparse_peak_bytes"], cases[512]["sparse_peak_bytes"])
d128, d512 = (cases[128]["dense_peak_bytes"], cases[512]["dense_peak_bytes"])
assert None not in (s128, s512, d128, d512), rep
assert s512 / s128 < 8.0, (s128, s512)
assert s512 / s128 < d512 / d128, (s128, s512, d128, d512)
print("bench_out/BENCH_workers_smoke.json:",
      ", ".join(f"N={n}: {cases[n]['speedup']}x, "
                f"peak {cases[n]['sparse_peak_bytes']/1e3:.0f}kB sparse / "
                f"{cases[n]['dense_peak_bytes']/1e3:.0f}kB dense"
                for n in (128, 256, 512)))
EOF

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 9 regression tests: sparse engine + worker sharding =="
python -m pytest -q -m "not slow" tests/test_sparse.py
fi

echo "== ISSUE 10 smoke: fused RDP accountant (train 3 rounds, rdp <= composition) =="
ACCT_RUNDIR="bench_out/runlogs_accounting"
rm -rf "$ACCT_RUNDIR" && mkdir -p "$ACCT_RUNDIR"
python -m repro.launch.train \
    --arch dwfl-paper --steps 3 --workers 6 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense \
    --chunk-rounds 2 --eval-every 0 --accountant rdp \
    --runlog-dir "$ACCT_RUNDIR"
python - "$ACCT_RUNDIR" <<'EOF'
import json, pathlib, sys
run = next(pathlib.Path(sys.argv[1]).iterdir())
reps = [json.loads(l) for l in (run / "events.jsonl").open()
        if json.loads(l)["type"] == "epsilon_report"]
assert len(reps) == 1, reps
r = reps[0]
# the fused Rényi ledger must quote a budget <= the legacy composition
# quote, and the headline min-quote must spend exactly the protocol δ
assert r["eps_rdp"] <= r["eps_composed"], r
assert r["eps_total"] <= r["eps_rdp"] + 1e-12, r
assert r["accountant"] == "rdp" and not r["saturated"], r
print(f"{run.name}: rdp={r['eps_rdp']:.3g} <= "
      f"composition={r['eps_composed']:.3g} "
      f"(gap {r['accountant_gap']:.2g}x)")
EOF

echo "== ISSUE 10 smoke: rdp total-budget sigma calibration =="
python -m repro.launch.train \
    --arch dwfl-paper --steps 3 --workers 6 --batch-size 8 \
    --channel-model dynamic --scenario iot_dense \
    --chunk-rounds 2 --eval-every 0 --accountant rdp --total-epsilon 4.0

echo "== ISSUE 10 smoke: accountant gap artifact (T in 32/128/512) =="
# asserts the >= 15% acceptance at T = 512 itself (the gap is analytic)
python -m benchmarks.accounting_bench --smoke
python - <<'EOF'
import json
rep = json.load(open("bench_out/BENCH_accounting_smoke.json"))
cases = {c["T"]: c for c in rep["cases"]}
assert set(cases) == {32, 128, 512}, rep
assert cases[512]["eps_gap"] >= 1.15, cases[512]
assert cases[512]["sigma_saving"] >= 1.15, cases[512]
print("bench_out/BENCH_accounting_smoke.json:",
      ", ".join(f"T={t}: eps gap {cases[t]['eps_gap']:.1f}x, "
                f"sigma saving {cases[t]['sigma_saving']:.1f}x"
                for t in (32, 128, 512)))
EOF

if [[ "$RUN_REGRESSION" == 1 ]]; then
echo "== ISSUE 10 regression tests: accountant + calibration guard =="
python -m pytest -q tests/test_accounting.py
python -m pytest -q tests/test_obs.py::test_eps_moments_compose_like_heterogeneous \
    tests/test_privacy.py::test_property_calibration_roundtrip
fi

echo "ci_check: OK"
