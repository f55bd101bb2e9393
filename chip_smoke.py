#!/usr/bin/env python3
"""Chip smoke test: drive the DWFL main path on a TPU and check what comes out.

Everything runs in this one process (a chip belongs to one process): each
phase calls ``repro.launch.train.main(argv)``, the entry point a user runs,
at the paper's width — dwfl-paper, an MLP of 3072-256-256-10 with 855,050
parameters per worker — through the scan-chunk engine, the persistent flat
[W, d] buffer and the fused dp_mix round, with the in-scan ε ledger on.
Weights and data are random, made from ``--seed 0``.

    python chip_smoke.py             # one chip: phases (a)-(d) and the kernel checks
    python chip_smoke.py --chips 4   # four chips: the mesh paths against one device

One chip:
  (a) static channel, N = 10, 4-round chunks, with an eval;
  (b) dynamic channel, iot_dense scenario;
  (c) the replicate fleet, R = 8, on the dynamic flat path;
  (d) the sparse neighbor-list round at N = 1024, k = 10 (mesh_sparse);
  (k) the dp_mix kernel alone: the noiseless round against kernels/dp_mix/
      ref.py at f32 tolerance, and the on-chip noise draws' moments.
Each training phase checks that loss, buffer and ε quote are finite, that
every chunk length compiled exactly once, and what the chunk program mixes
with: the Pallas kernel (a tpu_custom_call in its HLO) on the dense paths,
an XLA gather and no kernel on the sparse one.

Four chips (--chips 4): --model-shards 4 (static and dynamic) and
--worker-shards 4 with the sparse round, each against the same run on one
device — at f32 tolerance under the noiseless gossip scheme, and by
finiteness and buffer moments with the DP noise on.

Earlier lines report the device kind, compile seconds per chunk length and
steady seconds per chunk. The last line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}},
printed only when every phase passed. Without a TPU the script exits 2
before running anything; a failed phase exits 1.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
N_PAPER = 10          # Sec. V's worker count at the paper's width
N_SPARSE = 1024
N_WORKER_SHARDS = 64  # sparse rows of the four-chip worker-axis comparison
# The sparse chunk program keeps ~20 [N, d] f32 buffers live (a v5e
# compile of it at N = 256, hidden 48 reports temporaries of 18.9x the
# buffer: grads, padded copies, noise fields, the gathered neighbor rows).
# At N = 1024 and the full d = 855,050 that is ~66 GB, over the chip's
# 16 GB of HBM; hidden = 48 makes d = 150,346 and ~12 GB.
SPARSE_HIDDEN = 48
TIMED_CHUNKS = 3


class PhaseFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailure(msg)


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def common_argv(steps, eval_every, chunk, seed=0):
    return ["--arch", "dwfl-paper", "--telemetry", "on",
            "--accountant", "rdp", "--seed", str(seed), "--steps", str(steps),
            "--eval-every", str(eval_every), "--chunk-rounds", str(chunk)]


def run_phase(name, argv, expect_kernel, chunk):
    """train.main(argv), then TIMED_CHUNKS more chunks of ``chunk`` rounds
    on the compiled program, timed to block_until_ready; then the checks.
    ``expect_kernel``: the chunk program mixes through the Pallas kernel
    (dense) rather than an XLA gather (sparse)."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.launch import train

    log(f"{name}: train {' '.join(argv)}")
    res = train.main(argv)
    runner, carry = res.runner, res.carry
    dog = obs.RetraceWatchdog(runner, label=name)
    dog.check()
    steady = []
    for _ in range(TIMED_CHUNKS):
        t0 = time.perf_counter()
        carry, out = runner.run(carry, chunk)
        jax.block_until_ready(carry.params)
        steady.append(time.perf_counter() - t0)
    counts = runner.trace_counts()
    check(dog.check() == 0 and counts
          and all(n == 1 for n in counts.values()),
          f"{name}: chunk compile counts {counts} (each length must "
          f"compile exactly once)")
    text = runner.lowered_text(carry, chunk)
    has_kernel = "tpu_custom_call" in text
    if expect_kernel:
        check(has_kernel, f"{name}: no tpu_custom_call in the chunk HLO")
    else:
        check(not has_kernel and "gather" in text,
              f"{name}: the sparse chunk should mix by XLA gather without "
              f"a Pallas kernel (tpu_custom_call={has_kernel})")
    loss = float(jnp.mean(out["metrics"]["loss"]))
    buf_ok = bool(jnp.all(jnp.isfinite(carry.params)))
    check(math.isfinite(loss), f"{name}: loss {loss}")
    check(buf_ok, f"{name}: non-finite values in the flat buffer")
    check(math.isfinite(res.epsilon), f"{name}: eps quote {res.epsilon}")
    compile_s = {k: round(v, 3) for k, v in
                 sorted(runner.compile_seconds.items())}
    log(f"{name}: ok  compile_s/chunk_len={compile_s}  "
        f"steady_s/chunk({chunk} rounds)={statistics.median(steady):.4f} "
        f"(runs {[round(s, 4) for s in steady]})  loss={loss:.4f}  "
        f"eps={res.epsilon:.4g}  "
        f"mix={'pallas' if has_kernel else 'xla-gather'}  "
        f"buffer={tuple(carry.params.shape)}")
    return res, carry


def phase_static():
    run_phase("a static", common_argv(12, 4, 4)
              + ["--workers", str(N_PAPER)], True, 4)


def phase_dynamic():
    run_phase("b dynamic", common_argv(11, 0, 4)
              + ["--workers", str(N_PAPER), "--channel-model", "dynamic",
                 "--scenario", "iot_dense"], True, 4)


def phase_fleet():
    run_phase("c fleet", common_argv(7, 0, 4)
              + ["--workers", str(N_PAPER), "--channel-model", "dynamic",
                 "--scenario", "iot_dense", "--replicates", "8"], True, 4)


def sparse_argv(steps=5, chunk=2):
    return (common_argv(steps, 0, chunk)
            + ["--workers", str(N_SPARSE), "--hidden", str(SPARSE_HIDDEN),
               "--dataset-size", "40960", "--channel-model", "dynamic",
               "--scenario", "mesh_sparse", "--sparse-neighbors", "10",
               "--graph-fallback"])


def phase_sparse():
    h = SPARSE_HIDDEN      # 3072 -> h -> h -> 10, weights and biases
    d = 3072 * h + h + h * h + h + 10 * h + 10
    log(f"d sparse: --hidden {SPARSE_HIDDEN} (d={d}): at the full width "
        f"d=855050 the ~20 live [{N_SPARSE}, d] f32 buffers of the sparse "
        f"chunk program need ~66 GB of HBM, over the chip's 16 GB")
    run_phase("d sparse", sparse_argv(), False, 2)


def phase_kernel():
    """The fused round alone at the paper's shape ([10, 855050])."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.dp_mix import ops, ref

    N, d = N_PAPER, 855050
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    p = jax.random.normal(k1, (N, d), jnp.float32)
    g = jax.random.normal(k2, (N, d), jnp.float32)
    W = jax.random.uniform(k3, (N, N), jnp.float32)
    W = W / W.sum(axis=1, keepdims=True)
    amp = jnp.linspace(0.5, 2.0, N, dtype=jnp.float32)

    # 1. noiseless: the kernel against the plain reference at f32
    out = ops.dp_mix_round(p, g, 7, W, amp, 1.3, 0.5, gamma=0.05, eta=0.4,
                           noisy=False)
    with jax.default_matmul_precision("highest"):
        want = ref.dp_mix_round_ref(p, g, None, W, amp, 1.3, 0.5, gamma=0.05,
                                    eta=0.4, noisy=False)
    err = float(jnp.max(jnp.abs(out - want)))
    scale = float(jnp.max(jnp.abs(want)))
    check(err <= 1e-5 * scale + 1e-6,
          f"k kernel: noiseless max|kernel - ref| = {err:.3g} "
          f"(|ref| max {scale:.3g})")
    log(f"k kernel: noiseless round vs ref.py: max abs err {err:.3g} "
        f"(|ref| max {scale:.3g})")

    # 2. noise: W = I, amp = 1, c = 1, no self-correction, eta = 1 turns
    # the output into the DP draws themselves (p = g = 0); amp = 0 with
    # sigma_m = 1 and m_scale = 1 into the channel-noise draws
    zeros = jnp.zeros((N, d), jnp.float32)
    eye = jnp.eye(N, dtype=jnp.float32)
    ones, none = jnp.ones((N,), jnp.float32), jnp.zeros((N,), jnp.float32)

    def draws(seed, dp):
        return ops.dp_mix_round(zeros, zeros, seed, eye,
                                ones if dp else none, 1.0,
                                0.0 if dp else 1.0, gamma=0.0, eta=1.0,
                                self_scale=none, m_scale=ones, noisy=True)

    fields = {"dp s=11": draws(11, True), "dp s=12": draws(12, True),
              "channel s=11": draws(11, False)}
    for name, x in fields.items():
        x = np.asarray(x, np.float64).reshape(-1)
        mean, std = x.mean(), x.std()
        kurt = float(((x - mean) ** 4).mean() / std ** 4)
        check(abs(mean) < 5e-3 and abs(std - 1) < 5e-3
              and abs(kurt - 3) < 5e-2,
              f"k kernel: {name} draws mean {mean:.4g} std {std:.4g} "
              f"kurtosis {kurt:.4g}")
        log(f"k kernel: {name}: mean {mean:.3g} std {std:.5f} "
            f"kurtosis {kurt:.4f} over {x.size} draws")
    a, b = (np.asarray(fields[k]) for k in ("dp s=11", "channel s=11"))
    corr = float(np.corrcoef(a.reshape(-1), b.reshape(-1))[0, 1])
    check(abs(corr) < 5e-3, f"k kernel: DP/channel draws correlate {corr:.3g}")
    # no block of 512 columns may repeat, within a seed or across two
    bd, seen = 512, {}
    for name in ("dp s=11", "dp s=12"):
        x = np.asarray(fields[name])
        for j in range(d // bd):
            key = x[0, j * bd:j * bd + 8].tobytes()
            check(key not in seen, f"k kernel: block {j} of {name} repeats "
                  f"block {seen.get(key)}")
            seen[key] = (name, j)
    log(f"k kernel: noise ok — DP/channel correlation {corr:.2g}, "
        f"{len(seen)} distinct {bd}-column blocks over two seeds")


def four_chip_phases():
    """The mesh paths on four chips, each against the same run on one
    device. Where both runs draw the same noise (none under the gossip
    scheme; the sparse round's counter-hash stream, which worker shards
    tile exactly) the buffers agree at f32 tolerance. The dense kernel's
    noise blocks are seeded per column tile, and a model shard's tile
    differs from the whole buffer's, so with that noise on the two runs
    are compared by finiteness and buffer moments. The dynamic channel
    runs only the dwfl scheme (train refuses gossip there)."""
    import jax
    import numpy as np

    def final(argv):
        from repro.launch import train
        res = train.main(argv)
        return res, np.asarray(jax.device_get(res.params))

    def moments(x):
        return float(x.mean()), float(x.std())

    base = common_argv(4, 0, 4)
    static = base + ["--workers", str(N_PAPER)]
    dynamic = base + ["--workers", str(N_PAPER), "--channel-model",
                      "dynamic", "--scenario", "iot_dense"]
    # N_WORKER_SHARDS rows at the full width, split four ways
    sparse = base + ["--workers", str(N_WORKER_SHARDS), "--channel-model",
                     "dynamic", "--scenario", "mesh_sparse",
                     "--sparse-neighbors", "10", "--graph-fallback"]
    model4, worker4 = ["--model-shards", "4"], ["--worker-shards", "4"]
    cases = [
        ("static, model shards, gossip", static + ["--scheme", "gossip"],
         model4, True),
        ("static, model shards, dwfl", static, model4, False),
        ("dynamic, model shards, dwfl", dynamic, model4, False),
        ("sparse, worker shards, dwfl", sparse, worker4, True),
    ]
    for name, argv, mesh_args, exact in cases:
        # the f32 comparison runs the model's matmuls at f32 too (TPU's
        # default precision is one bf16 pass)
        with jax.default_matmul_precision("highest" if exact else "default"):
            t0 = time.perf_counter()
            _, one = final(argv)
            t1 = time.perf_counter()
            res4, four = final(argv + mesh_args)
            t2 = time.perf_counter()
        four = four[..., :one.shape[-1]]       # drop the layout's padding
        check(np.isfinite(one).all() and np.isfinite(four).all(),
              f"4 chips {name}: non-finite buffer")
        check(math.isfinite(res4.epsilon),
              f"4 chips {name}: eps quote {res4.epsilon}")
        if exact:
            err = float(np.max(np.abs(four - one)))
            tol = 1e-5 * float(np.max(np.abs(one))) + 3e-5
            check(err <= tol, f"4 chips {name}: max|4 chips - 1 device| "
                  f"= {err:.3g} > {tol:.3g}")
            detail = f"max abs diff {err:.3g} (tol {tol:.3g})"
        else:
            (m1, s1), (m4, s4) = moments(one), moments(four)
            check(abs(m4 - m1) <= 0.05 * s1 and abs(s4 / s1 - 1) <= 0.02,
                  f"4 chips {name}: buffer moments 1 device "
                  f"({m1:.4g}, {s1:.4g}) vs 4 chips ({m4:.4g}, {s4:.4g})")
            detail = (f"buffer mean/std 1 device ({m1:.4g}, {s1:.4g}) "
                      f"4 chips ({m4:.4g}, {s4:.4g})")
        compile_s = {k: round(v, 3)
                     for k, v in res4.runner.compile_seconds.items()}
        log(f"4 chips {name}: ok  {detail}  wall 1 device {t1 - t0:.1f}s, "
            f"4 chips {t2 - t1:.1f}s, 4-chip compile_s/chunk_len="
            f"{compile_s}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the one-chip phases; 4: the mesh phases only")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not beside this script "
              f"(no {ROOT / 'src' / 'repro'}); run it from a checkout",
              file=sys.stderr)
        return 2
    cache = setup_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found — JAX's backend is {backend!r}; "
              f"this smoke test only runs on the chip", file=sys.stderr)
        return 2
    devs = jax.devices()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    log(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache}")

    phases = ([("four chips", four_chip_phases)] if args.chips == 4 else
              [("a", phase_static), ("b", phase_dynamic),
               ("c", phase_fleet), ("d", phase_sparse), ("k", phase_kernel)])
    failed = []
    for label, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:          # report every phase, then fail
            import traceback
            traceback.print_exc()
            failed.append(label)
            log(f"phase {label} FAILED: {type(e).__name__}: {e}")
        log(f"phase {label}: {time.perf_counter() - t0:.1f}s wall")
    if failed:
        log(f"failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
