"""Model & input-shape configuration.

Every assigned architecture is expressed as a :class:`ModelConfig`; the four
assigned input shapes as :class:`InputShape`.  ``repro/configs/<arch>.py``
instantiates the exact published numbers and registers them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    vocab: int
    # attention (0 heads => attention-free)
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0               # 0 => d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 1e6
    # dense mlp
    d_ff: int = 0
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0               # per-expert hidden dim
    moe_groups_per_shard: int = 8   # token sub-groups per data shard (capacity locality)
    capacity_factor: float = 1.25
    # batch-sharded attention: when the head count is indivisible by the
    # model axis (attention weights replicated), reshard the *local batch*
    # over the model axis for the attention block so score/softmax transients
    # shrink by the axis size. Costs a [B,S,D] reshard in/out per layer.
    attn_batch_shard: bool = False
    # combine implementation: "gather" (baseline: per-token gather from the
    # expert-sharded buffer -> GSPMD all-gathers E*C*D) or "scatter" (expert-
    # side scatter-add -> GSPMD partial-scatters locally and all-reduces only
    # T*D — the optimal combine payload; see EXPERIMENTS.md §Perf).
    moe_combine: str = "gather"
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_n_groups: int = 1
    ssm_chunk: int = 128            # SSD intra-chunk quadratic block size
    # Mamba heads held (a tensor-parallel share); 0 => ssm_expand * d_model
    # / ssm_head_dim, the whole mixer
    ssm_n_heads: int = 0
    # hybrid (Zamba2): the layers whose Mamba input first receives the
    # shared attention block's output; ``num_mem_blocks`` shared blocks
    # alternate over them, each invocation with its own adapter of rank
    # ``adapter_rank`` and its own output ``linear``
    hybrid_layers: tuple = ()
    num_mem_blocks: int = 0
    adapter_rank: int = 0
    # long-context: ring-buffer KV cache window for decode (0 = full cache)
    sliding_window: int = 0
    # numerics
    norm_eps: float = 1e-6
    param_dtype: jnp.dtype = jnp.bfloat16
    compute_dtype: jnp.dtype = jnp.bfloat16
    # attention chunking (flash-style online softmax)
    q_chunk: int = 1024
    kv_chunk: int = 512
    # activation checkpointing on the layer scan (recompute in backward);
    # without it the backward pass stores every intra-layer intermediate of
    # every layer (e.g. the SSD chunk tensors), far beyond HBM.
    remat: bool = True
    # scan-over-layers (single HLO layer body; fast 512-device compiles).
    # False unrolls the stack in python — used by the roofline probes because
    # XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    # count (verified empirically), so exact FLOP/byte/collective counts need
    # an unrolled lowering (done at reduced depth and extrapolated).
    scan_layers: bool = True
    # frontends ([vlm]/[audio]): token ids are precomputed codebook ids (stub
    # per the carve-out); the backbone consumes ids like any LM.
    frontend: Optional[str] = None  # "vq_image" | "encodec" | None

    def __post_init__(self):
        # a JSON configuration gives a list; the config stays hashable
        object.__setattr__(self, "hybrid_layers", tuple(self.hybrid_layers))

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_head_dim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_n_groups * self.ssm_state

    @property
    def has_attention(self) -> bool:
        return self.n_heads > 0

    @property
    def is_recurrent(self) -> bool:
        """O(1)-in-seq decode state (SSM/hybrid) => long_500k is native."""
        return self.arch_type in ("ssm", "hybrid")

    def padded_vocab(self, multiple: int = 16) -> int:
        return ((self.vocab + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k":    InputShape("train_4k",    4_096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  InputShape("decode_32k",  32_768,  128, "decode"),
    "long_500k":   InputShape("long_500k",   524_288, 1,   "decode"),
}


def n_params(cfg: ModelConfig) -> int:
    """Analytic parameter count (matches init exactly)."""
    D, V = cfg.d_model, cfg.padded_vocab()
    total = V * D + D + D * V          # embed, final norm, lm head
    per_attn = 0
    if cfg.n_heads:
        hd = cfg.hd
        per_attn = D * cfg.n_heads * hd + 2 * D * cfg.n_kv_heads * hd + cfg.n_heads * hd * D
        if cfg.qk_norm:
            per_attn += 2 * hd
    per_mlp = 3 * D * cfg.d_ff if cfg.d_ff else 0
    per_moe = (D * cfg.n_experts + cfg.n_experts * 3 * D * cfg.moe_d_ff) if cfg.n_experts else 0
    per_mamba = 0
    if cfg.ssm_state:
        di, H, N, G = cfg.d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_n_groups
        cc = cfg.conv_channels
        per_mamba = (D * (2 * di + 2 * G * N + H)   # in_proj
                     + cfg.ssm_conv * cc + cc        # conv w+b
                     + 3 * H                         # A_log, D, dt_bias
                     + di                            # gated norm
                     + di * D)                       # out_proj

    if cfg.arch_type == "hybrid":
        total += cfg.n_layers * (per_mamba + D)      # mamba blocks + ln
        total += cfg.num_mem_blocks * shared_block_params(cfg)
        r, F = cfg.adapter_rank, cfg.d_ff
        total += len(cfg.hybrid_layers) * (D * r + 2 * r * F + D * D)
    elif cfg.arch_type == "ssm":
        total += cfg.n_layers * (per_mamba + D)
    elif cfg.arch_type == "moe":
        total += cfg.n_layers * (per_attn + per_moe + 2 * D)
    else:                                            # dense / vlm / audio
        total += cfg.n_layers * (per_attn + per_mlp + 2 * D)
    return total


def shared_block_params(cfg: ModelConfig) -> int:
    """One Zamba2 shared block: its two norms, attention reading the
    ``2 * d_model`` concatenation, and the GELU-gated MLP."""
    D, hd = cfg.d_model, cfg.hd
    attn = 2 * D * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * D
    return 2 * D + attn + D + 3 * D * cfg.d_ff


def n_active_params(cfg: ModelConfig) -> int:
    """Active parameters per token (MoE: top_k of n_experts)."""
    if not cfg.n_experts:
        return n_params(cfg)
    D = cfg.d_model
    dense_moe = cfg.n_layers * (D * cfg.n_experts + cfg.n_experts * 3 * D * cfg.moe_d_ff)
    active_moe = cfg.n_layers * (D * cfg.n_experts + cfg.top_k * 3 * D * cfg.moe_d_ff)
    return n_params(cfg) - dense_moe + active_moe
