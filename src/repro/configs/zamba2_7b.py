"""Zamba2-7B [hybrid]: 81 Mamba2 layers; at 13 of them two shared attention
blocks, fed with the embedding output, alternate ahead of the Mamba mixer,
each invocation with its own MLP adapter and linear.  [arXiv:2411.15242;
huggingface.co/Zyphra/Zamba2-7B-Instruct config.json]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b", arch_type="hybrid",
    n_layers=81, d_model=3584, vocab=32000,
    n_heads=32, n_kv_heads=32, head_dim=224, d_ff=14336,
    ssm_state=64, ssm_head_dim=64, ssm_expand=2, ssm_n_groups=2,
    ssm_chunk=256,
    hybrid_layers=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2, adapter_rank=128, rope_theta=1e4, norm_eps=1e-5,
)
