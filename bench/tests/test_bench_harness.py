"""The benchmark's yardstick and its lookup by name, on the CPU."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PAPER = json.loads((ROOT / "bench/configs/dwfl-paper-n10-static.json")
                   .read_text())


def flops():
    return run.load_module(ROOT / "bench/flops/mlp.py")


def test_train_flops_per_sample_by_hand():
    # P = 3072*256 + 256*256 + 256*10 = 854,528 matmul weights; forward
    # 2P, weight gradients 2P, input gradients 2(P - 786,432)
    assert flops().train_flops_per_sample(PAPER["model"]) == 3_554_304
    assert flops().params_per_worker(PAPER["model"]) == 855_050
    assert flops().round_flops(PAPER) == 10 * 32 * 3_554_304


def test_dp_mix_work_by_hand():
    f, b = flops().dp_mix_work(PAPER)
    assert b == (3 * 10 * 855_050 + 100) * 4
    assert f == 2 * 10 * 10 * 855_050


def test_peaks_refuse_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks_for("TPU v9 imaginary")


def test_off_the_tpu_the_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "paper_n10.sweep", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, timeout=300, env=env)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_without_the_program_the_run_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "paper_n10.sweep", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    cell = run.load_cell(name)
    cfg = cell["cfg"]
    assert cell["traffic"]["chunk_rounds"] >= 3
    assert cell["limits"], f"no limits for {name}"
    for kind, mod in (("reference", cfg["reference"]),
                      ("flops", cfg["flops"])):
        assert (ROOT / "bench" / kind / f"{mod}.py").exists()
    for m in cell["per_layer"]:
        assert hasattr(run.part(cell, "metrics", m["name"]), "read")
    assert {m["name"] for m in cell["end_to_end"]} >= {"rounds_per_s",
                                                       "setup_s"}
    assert cell["per_layer"]


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads(json.dumps(PAPER))
    cfg["name"] = "dwfl-paper-n30-static"
    cfg["protocol"]["workers"] = 30
    (tmp_path / "bench/configs/dwfl-paper-n30-static.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/sweep_k7.json").write_text(
        json.dumps({"chunk_rounds": 7, "readback": False}))
    (tmp_path / "bench/limits/paper_n30.sweep.json").write_text(
        json.dumps({"limits": {"loss0_rel": 1e-4}}))
    (tmp_path / "bench/metrics/rounds_traced.py").write_text(
        "def read(summary, ctx):\n    return float(ctx['rounds'])\n")
    spec["configs"].append({"name": "dwfl-paper-n30-static",
                            "source": "https://arxiv.org/abs/2109.09142",
                            "file": "bench/configs/dwfl-paper-n30-static.json",
                            "reduced": [], "why": "N = 30"})
    spec["workloads"].append({"name": "paper_n30.sweep",
                              "config": "dwfl-paper-n30-static",
                              "traffic": "sweep_k7", "chips": 1,
                              "why": "N = 30"})
    spec["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "rounds_per_s",
                              "workloads": ["paper_n30.sweep"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell("paper_n30.sweep", tmp_path)
    assert cell["cfg"]["protocol"]["workers"] == 30
    assert cell["traffic"]["chunk_rounds"] == 7
    assert cell["limits"] == {"loss0_rel": 1e-4}
    assert [m["name"] for m in cell["per_layer"]] == ["rounds_traced"]
    got = run.per_layer(cell, {"rounds": 14, "chunks": 2, "summary": None},
                        "TPU v5 lite")
    assert got == {"rounds_traced": {"value": 14.0, "unit": "rounds"}}
    argv = run.train_argv(cell["cfg"], cell["traffic"], 5)
    assert argv[argv.index("--workers") + 1] == "30"
    assert argv[argv.index("--chunk-rounds") + 1] == "7"
    assert argv[argv.index("--steps") + 1] == "-1"
