"""Architecture registry: the 10 assigned configs + the paper's own models.

``get_config(arch_id)`` returns the exact published configuration;
``smoke_config(cfg)`` returns the reduced variant used by the CPU smoke tests
(<=2 layers, d_model<=512, <=4 experts).
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "zamba2-7b": "zamba2_7b",
    "qwen3-8b": "qwen3_8b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "yi-6b": "yi_6b",
    "mamba2-130m": "mamba2_130m",
    "chameleon-34b": "chameleon_34b",
    "musicgen-medium": "musicgen_medium",
    "yi-9b": "yi_9b",
    "phi3.5-moe-42b-a6.6b": "phi3p5_moe_42b",
    "stablelm-1.6b": "stablelm_1p6b",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests."""
    kw = dict(
        name=cfg.name + "-smoke", n_layers=2, d_model=256,
        vocab=min(cfg.vocab, 512),
        q_chunk=32, kv_chunk=16,
    )
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=min(4, max(1, cfg.n_kv_heads)),
                  head_dim=32, d_ff=256 if cfg.d_ff else 0)
    if cfg.n_experts:
        kw.update(n_experts=4, top_k=min(2, cfg.top_k), moe_d_ff=64)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_head_dim=32)
    if cfg.hybrid_layers:
        # both layers hybrid, so each of (at most) two shared blocks runs
        kw.update(hybrid_layers=(0, 1), num_mem_blocks=min(cfg.num_mem_blocks, 2),
                  adapter_rank=8)
    if cfg.sliding_window:
        kw.update(sliding_window=64)
    return dataclasses.replace(cfg, **kw)
