"""zamba2-7b [hybrid] -- Zamba2-7B-Instruct as published
(https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json,
arXiv:2411.15242): 81 Mamba2 layers (d 3584, d_inner 7168, 112 heads of 64,
2 B/C groups, d_state 64, conv 4, chunk 256) and 2 weight-shared
attention+MLP blocks used in turn before the mamba layers at
hybrid_layer_ids.  A shared block reads RMSNorm([x ; token embedding])
(7168 wide), attends with 32 heads of 224 under rotary positions, runs a
gated-GELU MLP of 14336 with a rank-128 LoRA on gate/up per invocation,
and a per-invocation linear maps its output into the next mamba layer's
input: x <- x + Mamba2(RMSNorm(x + t)).  Tied embeddings (Zamba2Config's
default); softmax scale (224 / 2) ** -0.5 and exact GELU as in the Zamba2
modelling code."""
from .base import ArchConfig

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


def config() -> ArchConfig:
    return ArchConfig(
        name="zamba2-7b", family="hybrid", citation="arXiv:2411.15242",
        n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
        d_ff=14336, vocab_size=32000, mlp_act="geglu", tie_embeddings=True,
        rope_theta=10000.0, norm_eps=1e-5, attn_scale=112 ** -0.5,
        ssm_state=64, ssm_conv=4, d_inner_mult=2, ssm_heads=112, ssm_groups=2,
        ssm_chunk=256, hybrid_layer_ids=HYBRID_LAYER_IDS, n_shared_blocks=2,
        adapter_rank=128, sliding_window=0,
    )


def smoke() -> ArchConfig:
    return config().replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=128, vocab_size=512, attn_scale=16 ** -0.5, ssm_state=16,
        ssm_heads=8, ssm_chunk=16, hybrid_layer_ids=(1, 3), adapter_rank=8,
        dtype="float32")
