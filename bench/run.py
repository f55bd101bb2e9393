#!/usr/bin/env python3
"""Benchmark harness of the DWFL chunk trainer: one cell, one run.

    python3 bench/run.py --workload paper_n10.sweep --seed 7 --seconds 10 \
        --trace 0

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. Everything about a cell is data found by name: the workload in
``BENCHMARK.json`` names a configuration (``bench/configs/<config>.json``:
the sizes, the protocol, the channel, the reference and work-count
modules; ``FLAGS`` says which of its keys reach the trainer, and the
file's own ``described`` lists the keys no flag takes) and a traffic
mix (``bench/traffic/<traffic>.json``: chunk length and what the window
reads back); its limits are ``bench/limits/<workload>.json`` and
each per-layer metric is a reader ``bench/metrics/<metric>.py``.

A run:

1. Set-up. JAX's compile cache at its fixed path in the checkout; the
   chips checked; ``repro.launch.train.main`` builds the trainer from the
   seed (data, split, weights, channel) and runs no round; the first chunk
   then goes through the window's own call, ``runner.run(carry, K)``, which
   compiles it (or loads it from the cache). That chunk's readings (its
   per-round loss and gradient norm, the state it leaves, the epsilon
   ledger) are kept for the comparison.
2. Window. ``runner.run(carry, K)`` back to back for ``--seconds``; the
   curve traffic reads each chunk's telemetry back and quotes epsilon, as
   the trainer does for its run log. The window ends at
   ``block_until_ready`` of the last chunk, and a compile inside it fails
   the run.
3. Check. With the program's state freed, the plain reference follows the
   same first rounds from the seed (``bench/check.py``).
4. The last line of standard output is one JSON object; each number
   compared is printed beside its limit as the last lines of standard
   error. ``--trace 1`` reports the per-layer metrics from a profiler trace
   of the window instead of the end-to-end ones.

Exit 2 without a TPU (or with fewer chips than the cell asks for) or
without the program beside ``bench/``; exit 3 if the window compiled.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class BenchError(Exception):
    """A run that cannot report: printed, no result line, non-zero exit."""

    def __init__(self, msg, code=1):
        super().__init__(msg)
        self.code = code


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell

def load_module(path):
    """A module of the benchmark's own, loaded from its file."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name, root=ROOT):
    """The workload's entry with its configuration, traffic, limits and
    metrics, all found by name under ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    limits_file = root / "bench" / "limits" / f"{name}.json"
    limits = (json.loads(limits_file.read_text())["limits"]
              if limits_file.exists() else {})

    def mine(m):
        return name in m.get("workloads", [name])
    return {"name": name, "root": root, "chips": w["chips"], "cfg": cfg,
            "traffic": traffic, "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def part(cell, kind, name):
    """bench/<kind>/<name>.py of the cell's checkout."""
    return load_module(cell["root"] / "bench" / kind / f"{name}.py")


# What of a configuration's groups reaches the trainer: each key with its
# flag, in the order of the command line, and its kind. A required key
# must be in the file; an optional one maps to its flag only where the
# file gives it, and the trainer keeps its own default otherwise (without
# ``hidden`` the architecture keeps its own width); a switch is true or
# false, and true gives the bare flag.
REQUIRED, OPTIONAL, SWITCH = "required", "optional", "switch"
FLAGS = (
    ("model", "arch", "--arch", REQUIRED),
    ("model", "hidden", "--hidden", OPTIONAL),
    ("model", "seq_len", "--seq-len", OPTIONAL),
    ("protocol", "scheme", "--scheme", REQUIRED),
    ("protocol", "workers", "--workers", REQUIRED),
    ("protocol", "batch_size", "--batch-size", REQUIRED),
    ("data", "size", "--dataset-size", OPTIONAL),
    ("data", "dirichlet_alpha", "--dirichlet-alpha", OPTIONAL),
    ("protocol", "gamma", "--gamma", REQUIRED),
    ("protocol", "eta", "--eta", REQUIRED),
    ("protocol", "clip", "--clip", REQUIRED),
    ("protocol", "epsilon", "--epsilon", REQUIRED),
    ("protocol", "sigma_m", "--sigma-m", REQUIRED),
    ("protocol", "p_dbm", "--p-dbm", REQUIRED),
    ("protocol", "accountant", "--accountant", REQUIRED),
    ("protocol", "model_shards", "--model-shards", OPTIONAL),
    ("protocol", "max_chunk_cols", "--max-chunk-cols", OPTIONAL),
    ("channel", "model", "--channel-model", REQUIRED),
    ("protocol", "remat", "--remat", SWITCH),
)


def check_keys(cfg):
    """Every key of the groups the trainer reads is a flag's or one that
    the configuration's own ``described`` lists (the sizes and precisions
    that the reference and the work counts read and no flag takes): a
    misspelt key would otherwise leave the trainer at its default and
    measure something else."""
    described = cfg.get("described", {})
    for group in dict.fromkeys(g for g, *_ in FLAGS):
        if not isinstance(cfg.get(group), dict):
            raise BenchError(f"the configuration has no {group!r} group")
        known = {k for g, k, *_ in FLAGS if g == group}
        unknown = sorted(set(cfg[group]) - known
                         - set(described.get(group, ())))
        if unknown:
            raise BenchError(f"the configuration's {group!r} group has keys "
                             f"that no flag takes and its 'described' does "
                             f"not list: {unknown}")


def train_argv(cfg, traffic, seed):
    """``launch/train.py``'s command line for the configuration
    (``FLAGS``): the flat buffer, the in-scan telemetry and RDP ledger,
    chunks of the traffic's length, no eval, and no round run (``--steps
    -1``: rounds are steps + 1) so that the first chunk goes through the
    window's own call."""
    check_keys(cfg)
    argv = []
    for group, key, flag, kind in FLAGS:
        if key not in cfg[group]:
            if kind == REQUIRED:
                raise BenchError(f"the configuration gives no {group}.{key} "
                                 f"({flag})")
            continue
        value = cfg[group][key]
        if kind != SWITCH:
            argv += [flag, str(value)]
        elif not isinstance(value, bool):
            raise BenchError(f"{group}.{key} is {value!r}, not true or "
                             f"false")
        elif value:
            argv.append(flag)
    return argv + ["--flat-buffer", "--telemetry", "on",
                   "--seed", str(seed), "--steps", "-1", "--eval-every", "0",
                   "--chunk-rounds", str(traffic["chunk_rounds"])]


# -------------------------------------------------------------- set-up

def init_jax(require_tpu, chips):
    """Compile cache and the device check; returns jax."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        raise BenchError(f"the program is not beside bench/ "
                         f"({ROOT / 'src' / 'repro'}): {e}", 2)
    setup_compile_cache()
    import jax
    # every program of a cell, however small, is found in the cache by the
    # next run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if require_tpu:
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's devices are {devs[0].platform}",
                             2)
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX has "
                             f"{len(devs)}", 2)
    return jax


def setup(cell, seed):
    """Build the trainer from the seed and drive it through its first
    chunk; keep what the check needs. Returns the live state."""
    import jax
    import numpy as np
    from repro.launch import train
    cfg, traffic = cell["cfg"], cell["traffic"]
    ref = part(cell, "reference", cfg["reference"])
    K = traffic["chunk_rounds"]
    with contextlib.redirect_stdout(sys.stderr):
        res = train.main(train_argv(cfg, traffic, seed))
    runner, carry = res.runner, res.carry
    p0 = carry.params[0] + 0.0          # the shared initial row, kept
    carry, out = runner.run(carry, K)
    spec = program_layout(cfg)
    leaves = jax.tree_util.tree_leaves(spec.unravel(carry.params))
    leaf, dev = ref.state_stats(
        leaves, jax.tree_util.tree_leaves(spec.unravel(p0)))
    eps, counted = ref.ledger_eps(np.asarray(carry.eps),
                                  cfg["protocol"]["delta"])
    first = {"loss": np.asarray(out["metrics"]["loss"], np.float64).tolist(),
             "grad_norm": np.asarray(out["metrics"]["grad_norm"],
                                     np.float64).tolist(),
             "change_leaf": ref.keyed([x.shape[1:] for x in leaves],
                                      np.asarray(leaf)),
             "row_dev": np.asarray(dev, np.float64), "eps": eps,
             "rounds": counted}
    live = {"runner": runner, "carry": carry, "first": first}
    if traffic["readback"]:
        live["point"] = curve_point(cfg)
        live["point"](carry, out)
    del p0, leaves, leaf, dev
    jax.block_until_ready(carry.params)
    return live


def program_layout(cfg):
    """The program's flat-buffer layout for the configuration, through
    which its buffer is read leaf by leaf: the architecture resolved as the
    trainer resolves it. Under ``model_shards`` the buffer is padded at its
    end, past every leaf, so this unsharded layout reads its leaves too."""
    import dataclasses
    import jax
    from repro.configs.registry import get_arch
    from repro.core import exchange as X
    from repro.core import protocol as P
    model = cfg["model"]
    arch = get_arch(model["arch"])
    if "hidden" in model:
        arch = dataclasses.replace(arch, d_model=model["hidden"])
    return X.make_flat_spec(jax.eval_shape(
        lambda: P.init_worker_params(jax.random.PRNGKey(0), arch,
                                     cfg["protocol"]["workers"])))


def curve_point(cfg):
    """What the trainer does for its run log after a chunk: the chunk's
    telemetry rows to the host and the trajectory epsilon composed from
    the ledger."""
    import numpy as np
    from repro.core import privacy
    delta = cfg["protocol"]["delta"]

    def point(carry, out):
        rows = np.asarray(out["telemetry"])
        eps, _ = privacy.compose_from_moments(np.asarray(carry.eps), delta,
                                              accountant="rdp")
        return rows, float(eps)
    return point


# -------------------------------------------------------------- window

def window(live, traffic, seconds, span):
    """Chunks back to back for ``seconds``; with the curve traffic each is
    followed by its telemetry readback and the epsilon quote. Returns the
    counts and times; ``live`` keeps the final carry."""
    import jax
    import numpy as np
    runner, carry = live["runner"], live["carry"]
    K = traffic["chunk_rounds"]
    point = live.get("point")
    losses, intervals, bad_points = [], [], 0
    before = dict(runner.trace_counts())
    with span("bench.window"):
        t0 = last = time.perf_counter()
        pending = None
        while True:
            with span("bench.dispatch"):
                carry, out = runner.run(carry, K)
            losses.append(out["metrics"]["loss"])
            if point is not None:
                with span("bench.wait"):
                    jax.block_until_ready(out["telemetry"])
                with span("bench.readback"):
                    rows, eps = point(carry, out)
                bad_points += not (np.all(np.isfinite(rows))
                                   and np.isfinite(eps))
                now = time.perf_counter()
                intervals.append(now - last)
                last = now
            else:
                if pending is not None:
                    with span("bench.wait"):
                        jax.block_until_ready(pending)
                pending = out["metrics"]["loss"]
                now = time.perf_counter()
            if now - t0 >= seconds:
                break
        with span("bench.wait"):
            jax.block_until_ready(out["metrics"]["loss"])
        t1 = time.perf_counter()
    live["carry"] = carry
    if runner.trace_counts() != before:
        raise BenchError(f"the window compiled: chunk programs "
                         f"{before} -> {runner.trace_counts()}", 3)
    failed = sum(K for a in losses if not np.all(np.isfinite(np.asarray(a))))
    return {"chunks": len(losses), "rounds": K * len(losses),
            "seconds": t1 - t0, "intervals": intervals,
            "failed": max(failed, K * bad_points)}


def traced_window(live, traffic, seconds, keep=None):
    """The window under the profiler, reduced to a trace Summary."""
    import jax
    from bench import trace
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            res = window(live, traffic, seconds, jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        path = trace.find_trace(tmp)
        res["summary"] = trace.read(path)
        res["trace_bytes"] = path.stat().st_size
        if keep:
            shutil.copy(path, keep)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return res


# ------------------------------------------------------------- the check

def free_device():
    """Drop every array the program left on the device, so the reference
    runs in the memory the program held."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def reference_check(cell, seed, first):
    """The plain reference over the first chunk, compared with the
    program's readings."""
    from bench import check
    cfg = cell["cfg"]
    ref = part(cell, "reference", cfg["reference"])
    want = ref.run(cfg, seed, cell["traffic"]["chunk_rounds"])
    return check.compare(first, want)


# ------------------------------------------------------------- metrics

def device_info(jax, chips):
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def end_to_end(cell, res, setup_s):
    got = {"rounds_per_s": (res["rounds"] / res["seconds"], "rounds/s"),
           "setup_s": (setup_s, "s")}
    if len(res["intervals"]) >= 2:
        q = statistics.quantiles([1e3 * s for s in res["intervals"]], n=20)
        got["curve_interval_ms_p95"] = (q[18], "ms")
    return {m["name"]: {"value": got[m["name"]][0], "unit": m["unit"]}
            for m in cell["end_to_end"] if m["name"] in got}


def per_layer(cell, res, kind):
    cfg = cell["cfg"]
    ctx = {"cfg": cfg, "peak": peaks_for(kind, cell["root"]),
           "chips": cell["chips"], "rounds": res["rounds"],
           "chunks": res["chunks"], "flops": part(cell, "flops", cfg["flops"])}
    out = {}
    for m in cell["per_layer"]:
        reader = part(cell, "metrics", m["name"])
        value = reader.read(res["summary"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks_for(kind, root=ROOT):
    """The chip's published peaks; a kind not in the table is an error."""
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return peaks[kind]


# ---------------------------------------------------------------- main

def run(args, require_tpu=True, root=ROOT):
    cell = load_cell(args.workload, root)
    jax = init_jax(require_tpu, cell["chips"])
    traffic = cell["traffic"]
    live = setup(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s; window {args.seconds} s")
    if args.trace:
        res = traced_window(live, traffic, args.seconds, args.keep_trace)
    else:
        res = window(live, traffic, args.seconds,
                     lambda name: contextlib.nullcontext())
    device = device_info(jax, cell["chips"])
    first = live.pop("first")
    live.clear()
    free_device()
    values = reference_check(cell, args.seed, first)
    from bench import check, trace
    correct, lines, table = check.judge(values, cell["limits"])
    result = {"correct": correct, "attempted": res["rounds"],
              "failed": res["failed"]}
    if args.trace:
        s = res["summary"]
        result["metrics"] = per_layer(cell, res, device["kind"])
        device.update(busy_s=trace.busy_ns(s) * 1e-9, window_s=s.window_s)
        result["device"] = device
        result["breakdown"] = trace.breakdown(s)
        log(f"trace: {res['trace_bytes']} bytes, window {s.window_s:.3f} s, "
            f"{sum(len(o) for o in s.devices)} device ops")
    else:
        result["metrics"] = end_to_end(cell, res, setup_s)
        result["device"] = device
    result["checks"] = table
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None, require_tpu=True, root=ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    try:
        return run(args, require_tpu, root)
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return e.code


if __name__ == "__main__":
    sys.exit(main())
