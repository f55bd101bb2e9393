"""Work counts of DeepSeek-V2's layers (latent attention, the dense
SwiGLU, the DeepSeekMoE layer), from the configuration file's widths.

Counted as the algorithm needs them, on the unpadded shapes: causal
attention at S(S+1)/2 (query, key) pairs per sequence, each held expert on
the tokens routed to it (T * k * held / E pairs a layer, as a balanced
router gives), the head over every position. Remat's second forward and the
program's padding are not counted, so a change to either moves a share and
not this yardstick.
"""
from __future__ import annotations


def _w(cfg):
    """The widths of one worker's model as the file states them."""
    return {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
        "num_hidden_layers", "vocab_size")}


def _attention_params(w):
    d, H = w["hidden_size"], w["num_attention_heads"]
    qk = w["qk_nope_head_dim"] + w["qk_rope_head_dim"]
    r = w["kv_lora_rank"]
    return (d * H * qk + d * (r + w["qk_rope_head_dim"])
            + r * H * (w["qk_nope_head_dim"] + w["v_head_dim"])
            + H * w["v_head_dim"] * d)


def params_per_worker(cfg):
    """Length d of one worker's flat parameter row: every weight, norm
    scale and the latent norm, with the router at its published width and
    the held experts only."""
    w = _w(cfg)
    d, E = w["hidden_size"], cfg["reduced"]["n_routed_experts"]
    n_dense = w["first_k_dense_replace"]
    n_moe = w["num_hidden_layers"] - n_dense
    attn = _attention_params(w) + w["kv_lora_rank"] + 2 * d   # + norms
    dense = attn + 3 * d * w["intermediate_size"]
    moe = (attn + d * E
           + 3 * d * w["moe_intermediate_size"] * (w["n_routed_experts"]
                                                   + w["n_shared_experts"]))
    return n_dense * dense + n_moe * moe + 2 * w["vocab_size"] * d + d


def forward_flops(cfg):
    """Model FLOPs of one worker's forward pass over its batch."""
    w = _w(cfg)
    d, H = w["hidden_size"], w["num_attention_heads"]
    E = cfg["reduced"]["n_routed_experts"]
    B, S = cfg["protocol"]["batch_size"], cfg["model"]["seq_len"]
    T, pairs = B * S, B * S * (S + 1) // 2
    qk = w["qk_nope_head_dim"] + w["qk_rope_head_dim"]
    attn = (2 * T * _attention_params(w)
            + 2 * pairs * H * (qk + w["v_head_dim"]))
    dense = 2 * T * 3 * d * w["intermediate_size"]
    routed = (T * w["num_experts_per_tok"] * w["n_routed_experts"]) / E
    moe = (2 * T * d * E
           + 2 * routed * 3 * d * w["moe_intermediate_size"]
           + 2 * T * 3 * d * w["moe_intermediate_size"]
           * w["n_shared_experts"])
    n_dense = w["first_k_dense_replace"]
    n_moe = w["num_hidden_layers"] - n_dense
    return (n_dense * (attn + dense) + n_moe * (attn + moe)
            + 2 * T * d * w["vocab_size"])


def round_flops(cfg):
    """Model FLOPs of one DWFL round: every worker's forward and backward
    (twice the forward) over its batch."""
    return cfg["protocol"]["workers"] * 3 * forward_flops(cfg)


def dp_mix_work(cfg):
    """What one dense dp_mix round needs on [N, d] f32: FLOPs 2 N^2 d (the
    mix) and bytes (3 N d + N^2) * 4 (params and gradients read, the new
    buffer written, W read)."""
    N = cfg["protocol"]["workers"]
    d = params_per_worker(cfg)
    return 2 * N * N * d, (3 * N * d + N * N) * 4
