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


# launch/train.py's command line for dwfl-paper-n10-static with 5-round
# chunks and seed 7, as the harness built it before the configuration's
# keys became optional: the paper's cells must keep it to the element
PAPER_ARGV = [
    "--arch", "dwfl-paper", "--hidden", "256", "--scheme", "dwfl",
    "--workers", "10", "--batch-size", "32", "--dataset-size", "20000",
    "--dirichlet-alpha", "0.5", "--gamma", "0.01", "--eta", "0.4",
    "--clip", "1.0", "--epsilon", "1.0", "--sigma-m", "0.0",
    "--p-dbm", "60.0", "--accountant", "rdp", "--channel-model", "static",
    "--flat-buffer", "--telemetry", "on", "--seed", "7", "--steps", "-1",
    "--eval-every", "0", "--chunk-rounds", "5"]


def test_the_paper_configuration_keeps_its_command_line():
    cell = run.load_cell("paper_n10.curve")
    assert run.train_argv(cell["cfg"], cell["traffic"], 7) == PAPER_ARGV


def lm_config():
    cfg = json.loads(json.dumps(PAPER))
    del cfg["model"]["hidden"], cfg["data"]["size"]
    del cfg["data"]["dirichlet_alpha"]
    cfg["model"] = {"arch": "deepseek-moe-16b", "seq_len": 32,
                    "state": "float32"}
    cfg["protocol"].update(workers=4, model_shards=4, max_chunk_cols=4096,
                           remat=True)
    return cfg


def test_optional_keys_map_to_their_flags_only_where_given():
    argv = run.train_argv(lm_config(), {"chunk_rounds": 3}, 11)

    def flag(name):
        return argv[argv.index(name) + 1]
    assert flag("--seq-len") == "32"
    assert flag("--model-shards") == "4"
    assert flag("--max-chunk-cols") == "4096"
    assert "--remat" in argv
    for absent in ("--hidden", "--dataset-size", "--dirichlet-alpha"):
        assert absent not in argv
    cfg = lm_config()
    cfg["protocol"]["remat"] = False
    assert "--remat" not in run.train_argv(cfg, {"chunk_rounds": 3}, 11)


@pytest.mark.parametrize("group,key", [("model", "seqlen"),
                                       ("protocol", "model_shard"),
                                       ("data", "sizes"),
                                       ("channel", "modle")])
def test_a_key_the_harness_does_not_know_is_refused_by_name(group, key):
    cfg = lm_config()
    cfg[group][key] = 1
    with pytest.raises(run.BenchError, match=key):
        run.train_argv(cfg, {"chunk_rounds": 3}, 11)


def test_a_key_the_configuration_describes_reaches_no_flag():
    """A key no flag takes is the configuration's to list in its own
    ``described``: listed, it is kept out of the command line; not
    listed, it is refused by name."""
    cfg = lm_config()
    cfg["model"]["kv_lora_rank"] = 512
    cfg["described"]["model"] = [*cfg["described"]["model"], "kv_lora_rank"]
    argv = run.train_argv(cfg, {"chunk_rounds": 3}, 11)
    assert "512" not in argv
    assert argv == run.train_argv(lm_config(), {"chunk_rounds": 3}, 11)
    cfg["described"]["model"].remove("kv_lora_rank")
    with pytest.raises(run.BenchError, match="kv_lora_rank"):
        run.train_argv(cfg, {"chunk_rounds": 3}, 11)


def test_a_switch_that_is_not_true_or_false_is_refused():
    cfg = lm_config()
    cfg["protocol"]["remat"] = "false"
    with pytest.raises(run.BenchError, match="remat"):
        run.train_argv(cfg, {"chunk_rounds": 3}, 11)


def test_a_required_key_that_is_missing_is_refused_by_name():
    cfg = lm_config()
    del cfg["protocol"]["workers"]
    with pytest.raises(run.BenchError, match="protocol.workers"):
        run.train_argv(cfg, {"chunk_rounds": 3}, 11)


@pytest.mark.parametrize("shards", [2, 4])
def test_the_layout_reads_the_leaves_of_a_model_sharded_buffer(shards):
    """--model-shards pads the [W, d] buffer at its end, past every leaf
    (shard/layout.py); the unsharded layout the harness builds reads the
    sharded buffer's leaves as the sharded layout does."""
    import jax
    import numpy as np
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import exchange as X
    cfg = json.loads(json.dumps(PAPER))
    cfg["model"]["hidden"] = 40
    cfg["protocol"]["workers"] = 3
    spec = run.program_layout(cfg)
    rng = np.random.default_rng(shards)
    tree = jax.tree_util.tree_map(
        lambda x: rng.standard_normal(x.shape).astype(np.float32),
        jax.eval_shape(spec.unravel,
                       jax.ShapeDtypeStruct((3, spec.d), np.float32)))
    sharded = X.make_flat_spec(tree, n_shards=shards)
    buf = sharded.flatten(tree)
    assert buf.shape == (3, sharded.width) and sharded.width > spec.d
    want = jax.tree_util.tree_leaves(tree)
    for got in (jax.tree_util.tree_leaves(spec.unravel(buf)),
                jax.tree_util.tree_leaves(sharded.unravel(buf))):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
    # one row, as the set-up reads the shared initial row
    for g, w in zip(jax.tree_util.tree_leaves(spec.unravel(buf[0])), want):
        np.testing.assert_array_equal(np.asarray(g), w[0])
