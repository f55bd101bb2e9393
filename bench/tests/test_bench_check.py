"""``correct`` at a size a CPU test holds: a sound run passes, the control
fails, and a run with the timed path broken underneath fails once for each
fault a training cell can have. Each run is a subprocess, so the
harness's JAX settings stay out of the test process."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

# limits for the tiny cells below (N = 10, hidden 32, 4-round chunks, on
# the CPU, whose program multiplies in float32): round 0 exact to float32
# rounding; the mean step read 0.04-0.09 on sound runs and 1 or more with
# the step or the clip broken; row deviations 0.004-0.009 sound, about 1
# with the mix or the noise left out
TINY_LIMITS = {"loss0_rel": 1e-5, "grad_norm0_rel": 1e-5, "step_rel": 0.5,
               "row_dev_rel": 0.05, "eps_rel": 1e-5, "rounds_gap": 0}

BREAK = {
    # the round hands back the state it was given
    "unchanged": """
        orig = P.make_flat_train_step
        def make(cfg, proto, unravel_row):
            step = orig(cfg, proto, unravel_row)
            def broken(flat, batch, key):
                return flat, step(flat, batch, key)[1]
            return broken
        P.make_flat_train_step = make
    """,
    # half of each worker's batch left out, the mean taken over the rest
    "half_batch": """
        orig = P.make_flat_train_step
        def make(cfg, proto, unravel_row):
            step = orig(cfg, proto, unravel_row)
            def broken(flat, batch, key):
                half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
                return step(flat, half, key)
            return broken
        P.make_flat_train_step = make
    """,
    # the exchange between workers left out: the local step alone
    "no_exchange": """
        def local_only(p, g, seed, plan, *, gamma, eta, **kw):
            return p - gamma * g
        ops.dp_mix_round_plan = local_only
    """,
    # the gradient term left out of the fused round's update
    "no_step": """
        orig = ops.dp_mix_round_plan
        def no_step(p, g, seed, plan, **kw):
            return orig(p, 0.0 * g, seed, plan, **kw)
        ops.dp_mix_round_plan = no_step
    """,
    # the gradient handed on unclipped, with its true norm
    "no_clip": """
        from repro.core import privacy
        orig = privacy.clip_gradient_tree
        def unclipped(grads, g_max):
            return grads, orig(grads, g_max)[1]
        privacy.clip_gradient_tree = unclipped
    """,
    "sound": "",
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg["protocol"].update(workers=10, batch_size=8)
        cfg["data"]["size"] = 2000
        cfg["model"].update(hidden=32, matmul="float32")
        path.write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        t = root / "bench/traffic" / f"{w['traffic']}.json"
        traffic = json.loads(t.read_text())
        traffic["chunk_rounds"] = 4
        t.write_text(json.dumps(traffic))
        (root / "bench/limits" / f"{w['name']}.json").write_text(
            json.dumps({"limits": TINY_LIMITS}))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root, workload, fault):
    code = textwrap.dedent(f"""
        import json, pathlib, sys
        sys.path.insert(0, {str(ROOT / 'bench')!r})
        sys.path.insert(0, {str(ROOT / 'src')!r})
        import run
        from repro.core import protocol as P
        from repro.kernels.dp_mix import ops
    """) + textwrap.dedent(BREAK[fault]) + textwrap.dedent(f"""
        sys.exit(run.main(["--workload", {workload!r}, "--seed",
                           "2147483659", "--seconds", "0.3"],
                          require_tpu=False, root=pathlib.Path({str(root)!r})))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["sound", "unchanged", "half_batch",
                                   "no_exchange", "no_step", "no_clip"])
def test_a_broken_timed_path_is_not_correct(tiny_root, fault):
    out = run_cell(tiny_root, "paper_n10.sweep", fault)
    assert out["correct"] is (fault == "sound"), out["checks"]
    assert list(out)[-1] == "checks"


def test_a_sound_curve_run_is_correct(tiny_root):
    out = run_cell(tiny_root, "paper_n10.curve", "sound")
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["curve_interval_ms_p95"]["value"] > 0


def test_the_bfloat16_control_is_not_correct(tiny_root):
    code = textwrap.dedent(f"""
        import json, pathlib, sys
        sys.path.insert(0, {str(ROOT / 'bench')!r})
        import calibrate, run
        from bench import check
        cell = run.load_cell("paper_n10.sweep", pathlib.Path({str(tiny_root)!r}))
        rows = calibrate.control_readings(cell, 77)
        print(json.dumps({{k: check.judge(v, cell["limits"])[0]
                           for k, v in rows.items()}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    verdict = json.loads(p.stdout.strip().splitlines()[-1])
    # the reference on another noise stream stands for a sound run; the
    # control (one precision step down) and every planted fault fail
    assert verdict == {"stream": True, "control": False, "unchanged": False,
                       "half_batch": False, "no_exchange": False,
                       "no_step": False, "no_clip": False, "no_dp": False}
