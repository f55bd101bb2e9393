"""Configuration system for repro.

ModelConfig describes an architecture (one file per assigned arch in this
package); ShapeConfig describes an input workload; ProtocolConfig (in
repro.core.protocol) describes the DWFL wireless/privacy parameters.

All configs are frozen dataclasses so they can be closed over by jitted
functions and hashed as static arguments.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    # -- identity ----------------------------------------------------------
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""  # citation: arXiv id / model card

    # -- trunk dimensions ---------------------------------------------------
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None  # default d_model // num_heads (gemma: 256)

    # -- norm / mlp ---------------------------------------------------------
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm | nonparametric_ln
    mlp_type: str = "swiglu"  # swiglu | geglu | gelu
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d_model)

    # -- attention ----------------------------------------------------------
    rope_theta: float = 10000.0
    use_mrope: bool = False  # qwen2-vl M-RoPE
    mrope_sections: Tuple[int, ...] = (16, 24, 24)  # (t, h, w) per-half-dim split
    qkv_bias: bool = False  # qwen2 / glm4
    sliding_window: Optional[int] = None  # if set: sliding-window attention
    learned_pos_emb: bool = False  # whisper decoder/encoder
    # latent attention (deepseek-v2 MLA; repro.models.mla), on when
    # kv_lora_rank > 0: keys and values come from one compressed latent
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0  # rotated part of q, and the one shared k
    v_head_dim: int = 0
    # YaRN rope scaling (rope_scaling.type == "yarn"), on when rope_factor > 1
    rope_factor: float = 1.0
    rope_original_max_pos: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # -- MoE ----------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim (fine-grained experts)
    first_dense_layers: int = 0  # deepseek-moe: layer 0 is a dense FFN
    router_aux_weight: float = 0.01  # per MoE layer, summed over layers
    norm_topk_prob: bool = True  # renormalise the top-k gates to sum to 1
    routed_scaling_factor: float = 1.0
    # the chip's share of an expert-parallel layer: experts
    # [first_expert, first_expert + experts_held) are held here, the router
    # keeps all num_experts outputs (0 = every expert held)
    experts_held: int = 0
    first_expert: int = 0

    # -- SSM (mamba2 / xlstm) -----------------------------------------------
    ssm_state: int = 0  # N, state dim per head
    ssm_heads: int = 0  # number of SSM heads (defaults derived)
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 128  # chunk length for the SSD scan
    slstm_every: int = 0  # xlstm: every k-th block is sLSTM (0 = none)

    # -- hybrid (zamba2) ------------------------------------------------------
    shared_attn_every: int = 0  # apply the shared attention block every k SSM layers

    # -- encoder-decoder (whisper) -------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500  # whisper: 30 s of audio -> 1500 frames

    # -- modality stub (vlm / audio): inputs are precomputed embeddings -------
    embedding_inputs: bool = False

    # -- numerics -------------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = False  # activation checkpointing over the layer scan
    # distribution hints (require an active mesh; set by the dry-run/launch)
    tp_hints: bool = False  # pin the residual stream replicated across 'model'
    remat_policy: str = "full"  # full | dots (save dot outputs: no collective replay)

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def experts_local(self) -> int:
        """Routed experts this layer holds (all of them unless cut)."""
        return self.experts_held or self.num_experts

    @property
    def is_subquadratic(self) -> bool:
        """Can this config serve a 500k-token context (O(S) state, no dense KV)?"""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self, **kw) -> "ModelConfig":
        """A smoke-test-sized variant of the same family (2 layers, d<=512)."""
        small = dict(
            num_layers=2,
            d_model=min(self.d_model, 256),
            num_heads=min(self.num_heads, 4),
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            head_dim=64 if self.head_dim else None,
        )
        small["num_kv_heads"] = max(1, min(self.num_kv_heads,
                                           small["num_heads"],
                                           max(1, small["num_heads"] // max(1, self.num_heads // max(1, self.num_kv_heads)))))
        if self.num_experts:
            small.update(num_experts=4,
                         num_experts_per_tok=min(2, self.num_experts_per_tok),
                         num_shared_experts=min(1, self.num_shared_experts),
                         moe_d_ff=min(self.moe_d_ff, 128),
                         experts_held=0, first_expert=0)
        if self.ssm_state:
            small.update(ssm_state=16, ssm_heads=0, ssm_chunk=32)
        if self.slstm_every:
            small.update(slstm_every=2)
        if self.shared_attn_every:
            small.update(shared_attn_every=2)
        if self.is_encoder_decoder:
            small.update(num_encoder_layers=2, encoder_seq_len=64)
        if self.sliding_window:
            small.update(sliding_window=32)
        small.update(kw)
        return self.replace(**small)

    def cut(self, *, moe_layers: int, experts_held: int, vocab_size: int,
            first_expert: int = 0) -> "ModelConfig":
        """One chip's share of an expert-parallel deployment at the
        published widths (model-configs guide §4): the leading dense layers
        and ``moe_layers`` of the MoE layers after them (the rest lie on
        further pipeline stages), experts [first_expert, first_expert +
        experts_held) of each MoE layer (the router keeps every output) and
        a ``vocab_size`` slice of the vocabulary. No width changes. Refuses
        a cut below the guide's floors: 4 MoE layers, 8 routed experts, an
        eighth of the vocabulary."""
        if not self.num_experts:
            raise ValueError(f"{self.name} has no routed experts to hold")
        n_moe = self.num_layers - self.first_dense_layers
        if not 4 <= moe_layers <= n_moe:
            raise ValueError(f"moe_layers={moe_layers}: keep 4 to {n_moe}")
        if not 8 <= experts_held <= self.num_experts:
            raise ValueError(f"experts_held={experts_held}: hold 8 to "
                             f"{self.num_experts}")
        if not 0 <= first_expert <= self.num_experts - experts_held:
            raise ValueError(f"experts {first_expert}.."
                             f"{first_expert + experts_held - 1} are not all "
                             f"among the {self.num_experts}")
        if not self.vocab_size <= 8 * vocab_size <= 8 * self.vocab_size:
            raise ValueError(f"vocab_size={vocab_size}: keep an eighth "
                             f"({-(-self.vocab_size // 8)}) to "
                             f"{self.vocab_size}")
        return self.replace(
            num_layers=self.first_dense_layers + moe_layers,
            experts_held=(0 if experts_held == self.num_experts
                          else experts_held),
            first_expert=first_expert, vocab_size=vocab_size)


@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input workloads."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": ShapeConfig("prefill_32k", seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": ShapeConfig("decode_32k", seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": ShapeConfig("long_500k", seq_len=524288, global_batch=1, kind="decode"),
}
