"""zamba2-7b [hybrid] — 81 Mamba2 layers (d_model 3584, expand 2, 112
heads of 64, state 64, ngroups 2, chunk 256) and two shared transformer
blocks (32 heads of 224 over the 7168-wide concat of hidden state and
embedding, gated-GELU MLP 14336 with per-invocation rank-128 adapters)
applied at the 13 hybrid layers; vocab 32000.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json,
arXiv:2411.15242]
"""
from repro.configs.base import ModelConfig, SSMConfig, HybridConfig, register

CONFIG = register(ModelConfig(
    name="zamba2-7b",
    arch_type="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    mlp_activation="gelu",
    rope_theta=10000.0,
    norm_eps=1e-5,
    ssm=SSMConfig(state_dim=64, head_dim=64, expand=2, conv_width=4,
                  chunk=256, ngroups=2),
    hybrid=HybridConfig(layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59,
                                   65, 71, 77),
                        num_shared_blocks=2, adapter_rank=128),
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/"
           "config.json",
))
