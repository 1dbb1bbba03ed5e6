"""Model configuration.

Every assigned architecture is expressed as a :class:`ModelConfig`.
``repro/configs/<arch>.py`` instantiates the exact published numbers and
registers them.
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
    capacity_factor: float = 1.25
    # combine implementation: "gather" (baseline: per-token gather from the
    # expert-sharded buffer -> GSPMD all-gathers E*C*D) or "scatter" (expert-
    # side scatter-add -> GSPMD partial-scatters locally and all-reduces only
    # T*D — the optimal combine payload).
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

    def padded_vocab(self, multiple: int = 16) -> int:
        return ((self.vocab + multiple - 1) // multiple) * multiple


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
