"""The DeepSeek-V2-Lite cell's files against the program: the work counts'
row length is the program's, the widths the file states are the registered
cut's, and the cell at a CPU size (the file's structure at test widths,
the model axis sharded logically over 2 shards) is set up, driven through
its window and judged against the plain reference."""
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

CELL = "dsv2_lite_w4.sweep"
FILE = ROOT / "bench/configs/deepseek-v2-lite-w4.json"
# the file's names for the registered config's fields
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
          "moe_intermediate_size": "moe_d_ff",
          "num_experts_per_tok": "num_experts_per_tok",
          "n_shared_experts": "num_shared_experts",
          "first_k_dense_replace": "first_dense_layers",
          "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim", "rope_theta": "rope_theta",
          "norm_topk_prob": "norm_topk_prob",
          "routed_scaling_factor": "routed_scaling_factor",
          "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
          "n_routed_experts": "experts_local"}
YARN = {"factor": "rope_factor", "beta_fast": "rope_beta_fast",
        "beta_slow": "rope_beta_slow", "mscale": "rope_mscale",
        "mscale_all_dim": "rope_mscale_all_dim",
        "original_max_position_embeddings": "rope_original_max_pos"}


def _config():
    return json.loads(FILE.read_text())


def test_the_work_counts_row_is_the_programs():
    flops = run.load_module(ROOT / "bench/flops/deepseek_v2.py")
    cfg = _config()
    assert flops.params_per_worker(cfg) == run.program_layout(cfg).d \
        == 535_060_992


def test_the_files_widths_are_the_registered_cuts():
    from repro.configs.registry import get_arch
    cfg = _config()
    arch = get_arch(cfg["model"]["arch"])
    for key, field in FIELDS.items():
        assert cfg[key] == getattr(arch, field), key
    for key, field in YARN.items():
        assert cfg["rope_scaling"][key] == getattr(arch, field), key
    assert (cfg["model"]["first_expert"], cfg["model"]["aux_alpha"]) == (
        arch.first_expert, arch.router_aux_weight)
    # what the cut took away, at its published values
    full = get_arch("deepseek-v2-lite")
    assert cfg["reduced"] == {"num_hidden_layers": full.num_layers,
                              "n_routed_experts": full.num_experts,
                              "vocab_size": full.vocab_size}
    assert arch.num_experts == full.num_experts
    # the layer checkpoint has one source: the file's switch, not the arch
    assert cfg["protocol"]["remat"] is True and not arch.remat


# the tiny cell: the file's keys at test widths, 4 workers on 2 logical
# shards, 32 tokens a worker, 2-round chunks, float32 products (the CPU's)
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 96,
        "moe_intermediate_size": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
        "num_hidden_layers": 2, "n_routed_experts": 2,
        "num_experts_per_tok": 3, "vocab_size": 128}
# limits of the tiny cell: round 0 to float32 rounding; the mean step and
# the rows' spread read a few hundredths on sound runs at this size
TINY_LIMITS = {"loss0_rel": 1e-5, "grad_norm0_rel": 1e-5, "step_rel": 0.5,
               "row_dev_rel": 0.2, "eps_rel": 1e-5, "rounds_gap": 0}


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    import jax
    from repro.configs import deepseek_v2_lite, registry
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    cfg = _config()
    cfg.update(TINY)
    cfg["reduced"]["n_routed_experts"] = 8
    cfg["model"].update(arch="bench-test-dsv2", seq_len=32, first_expert=2,
                        matmul="float32")
    cfg["protocol"]["model_shards"] = 2
    (tmp_path / FILE.relative_to(ROOT)).write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/lm_sweep_k5.json").write_text(
        json.dumps({"chunk_rounds": 2, "readback": False}))
    (tmp_path / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"limits": TINY_LIMITS}))
    arch = deepseek_v2_lite.CONFIG.replace(
        name="bench-test-dsv2", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=96, moe_d_ff=32, num_experts=8,
        num_experts_per_tok=3, vocab_size=128, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        experts_held=2, first_expert=2, remat=True)
    monkeypatch.setitem(registry.ARCHS, "bench-test-dsv2", arch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield tmp_path
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_the_cell_runs_and_is_judged_against_the_reference(tiny_root):
    from bench import check
    seed = 2**31 + 29
    cell = run.load_cell(CELL, tiny_root)
    assert cell["chips"] == 4
    argv = run.train_argv(cell["cfg"], cell["traffic"], seed)
    assert "--remat" in argv and argv[argv.index("--model-shards") + 1] == "2"
    from repro.obs import spans
    spans.reset()
    readers = {name: run.part(cell, "metrics", name) for name in (
        "setup_params_s", "lm_setup_train_main_s", "lm_setup_compile_s")}
    assert all(r.read(None, {}) is None for r in readers.values())
    live = run.setup(cell, seed)
    # the set-up readers of the cell read the spans this set-up opened
    got = {name: r.read(None, {}) for name, r in readers.items()}
    assert 0 < got["setup_params_s"] < got["lm_setup_train_main_s"], got
    assert got["lm_setup_compile_s"] > 0, got
    first = live["first"]
    counts = dict(live["runner"].trace_counts())
    res = run.window(live, cell["traffic"], 0.5,
                     lambda name: contextlib.nullcontext())
    assert live["runner"].trace_counts() == counts
    assert res["rounds"] >= 2 and res["failed"] == 0
    values = run.reference_check(cell, seed, first)
    ok, lines, _ = check.judge(values, cell["limits"])
    assert ok, lines
