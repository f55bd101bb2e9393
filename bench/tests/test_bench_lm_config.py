"""A language-model configuration added by data files alone: a cell whose
configuration file gives a sequence length, a model-sharded buffer and its
architecture's widths, and no MLP keys, is built, set up and driven
through its window by the harness as it stands, on the CPU (the model
axis sharded logically on one device)."""
from __future__ import annotations

import contextlib
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))
import run  # noqa: E402

ARCH = "bench-test-moe-lm"
WORKERS, BATCH, SEQ, SHARDS = 4, 2, 32, 2
# the architecture's widths under the names of its published config, as a
# model's configuration file states them; no flag takes them
WIDTHS = {"hidden_size": 64, "num_hidden_layers": 2,
          "num_attention_heads": 4, "intermediate_size": 128,
          "moe_intermediate_size": 96, "n_routed_experts": 4,
          "num_experts_per_tok": 2, "n_shared_experts": 1,
          "first_k_dense_replace": 1, "vocab_size": 128}

CONFIG = {
    "name": "tiny-moe-lm",
    "source": "arXiv:2401.06066",
    "reduced": ["model"],
    "reference": "tiny_moe_lm",
    "flops": "mlp",
    "described": {"model": [*WIDTHS, "state", "matmul", "mix"],
                  "data": ["task"], "protocol": ["delta"],
                  "channel": ["fading", "noise_policy"]},
    "model": {"arch": ARCH, "seq_len": SEQ, **WIDTHS, "state": "float32",
              "matmul": "bfloat16", "mix": "float32"},
    "data": {"task": "synthetic"},
    "protocol": {"scheme": "dwfl", "workers": WORKERS, "batch_size": BATCH,
                 "gamma": 0.01, "eta": 0.4, "clip": 1.0, "epsilon": 1.0,
                 "delta": 1e-05, "sigma_m": 0.0, "p_dbm": 60.0,
                 "accountant": "rdp", "model_shards": SHARDS},
    "channel": {"model": "static", "fading": "rayleigh",
                "noise_policy": "surplus"},
}

# a configuration without a reference of its own yet reads the program's
# state with dwfl_mlp's generic readings
REFERENCE = ('"""The generic state readings, from dwfl_mlp."""\n'
             "from bench.reference.dwfl_mlp import (  # noqa: F401\n"
             "    keyed, ledger_eps, state_stats)\n")


@pytest.fixture
def lm_root(tmp_path, monkeypatch):
    """A checkout's bench/ with the LM cell's files added, the tiny
    architecture registered for the test's duration, and JAX's persistent
    compilation cache kept out of the checkout."""
    import jax
    from repro.configs import deepseek_moe_16b, registry
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": CONFIG["name"],
                            "source": CONFIG["source"],
                            "file": "bench/configs/tiny-moe-lm.json",
                            "reduced": ["model"], "why": "an LM"})
    spec["workloads"].append({"name": "tiny_lm.sweep",
                              "config": CONFIG["name"],
                              "traffic": "lm_sweep_k2", "chips": 1,
                              "why": "an LM"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/configs/tiny-moe-lm.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "bench/traffic/lm_sweep_k2.json").write_text(
        json.dumps({"chunk_rounds": 2, "readback": False}))
    (tmp_path / "bench/reference/tiny_moe_lm.py").write_text(REFERENCE)
    w = WIDTHS
    arch = deepseek_moe_16b.CONFIG.reduced(
        d_model=w["hidden_size"], num_layers=w["num_hidden_layers"],
        num_heads=w["num_attention_heads"],
        num_kv_heads=w["num_attention_heads"], d_ff=w["intermediate_size"],
        moe_d_ff=w["moe_intermediate_size"], num_experts=w["n_routed_experts"],
        num_experts_per_tok=w["num_experts_per_tok"],
        num_shared_experts=w["n_shared_experts"],
        first_dense_layers=w["first_k_dense_replace"],
        vocab_size=w["vocab_size"])
    monkeypatch.setitem(registry.ARCHS, ARCH, arch)
    # the trainer sets no cache directory where this is set, and the
    # test process writes no cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield tmp_path, arch
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_an_lm_configuration_is_added_by_data_files_alone(lm_root,
                                                          monkeypatch):
    import jax
    import numpy as np
    from repro.core import protocol as P
    from repro.launch import train
    root, arch = lm_root
    stores = []

    def store_from_batcher(batcher):
        stores.append(real(batcher))
        return stores[-1]
    real = train.store_from_batcher
    monkeypatch.setattr(train, "store_from_batcher", store_from_batcher)

    cell = run.load_cell("tiny_lm.sweep", root)
    argv = run.train_argv(cell["cfg"], cell["traffic"], 2**31 + 11)
    assert "--hidden" not in argv and "--dataset-size" not in argv
    # the widths the file states reach no flag
    assert "96" not in argv and "--moe-intermediate-size" not in argv
    live = run.setup(cell, 2**31 + 11)

    # the trainer's batches: W workers, B sequences of the file's length
    (store,) = stores
    tokens = store.sample(jax.random.PRNGKey(0))["tokens"]
    assert tokens.shape == (WORKERS, BATCH, SEQ)

    # the layout's leaves are the architecture's, read from the padded,
    # model-sharded buffer
    params = live["carry"].params
    spec = run.program_layout(cell["cfg"])
    assert params.shape[0] == WORKERS and params.shape[1] > spec.d
    want = jax.eval_shape(
        lambda: P.init_worker_params(jax.random.PRNGKey(0), arch, WORKERS))
    got = spec.unravel(params)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    assert ([x.shape for x in jax.tree_util.tree_leaves(got)]
            == [x.shape for x in jax.tree_util.tree_leaves(want)])
    # ... at the widths the file states
    w = WIDTHS
    shapes = {jax.tree_util.keystr(k): x.shape
              for k, x in jax.tree_util.tree_leaves_with_path(got)}
    assert shapes["['embed']['tok']"] == (WORKERS, w["vocab_size"],
                                          w["hidden_size"])
    assert shapes["['moe_blocks']['moe']['w_up']"] == (
        WORKERS, w["num_hidden_layers"] - w["first_k_dense_replace"],
        w["n_routed_experts"], w["hidden_size"], w["moe_intermediate_size"])
    assert shapes["['dense_blocks']['mlp']['w_up']"] == (
        WORKERS, w["first_k_dense_replace"], w["hidden_size"],
        w["intermediate_size"])
    first = live["first"]
    assert len(first["loss"]) == 2 and np.all(np.isfinite(first["loss"]))
    assert first["rounds"] == 2

    # the window runs the set-up's compiled chunk and compiles nothing
    # (a compile inside it raises)
    counts = dict(live["runner"].trace_counts())
    res = run.window(live, cell["traffic"], 1.0,
                     lambda name: contextlib.nullcontext())
    assert live["runner"].trace_counts() == counts
    assert res["rounds"] >= 2 and res["failed"] == 0
