"""zamba2-1.2b [hybrid] -- 38L d2048 ff8192 v32000 ssm_state=64; Mamba2
backbone with one weight-shared attention+MLP block invoked before every
sixth mamba layer [arXiv:2411.15242].  Same code path as zamba2_7b: each
invocation reads the residual stream concatenated with the token
embedding, and its per-invocation linear feeds the next mamba layer's
input.  long_500k adaptation: the shared block is windowed at
sliding_window for >64k decode budgets (DESIGN.md deviation).

Unconfirmed without the published config (no network here), kept at the
repo's earlier settings: 32 mamba heads of 128 (`mamba_headdim` may be 64,
PERF.md §7), one B/C group, one shared block, hybrid layers 6, 12, ..., 36,
no adapters (adapter_rank 0), a SwiGLU MLP, and 32 attention heads of 64.
The softmax scale (head_dim / 2) ** -0.5 is the Zamba2 modelling code's."""
from .base import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="zamba2-1.2b", family="hybrid", citation="arXiv:2411.15242",
        n_layers=38, d_model=2048, n_heads=32, n_kv_heads=32, d_ff=8192,
        vocab_size=32000, ssm_state=64, ssm_heads=32, ssm_groups=1,
        hybrid_layer_ids=tuple(range(6, 38, 6)), n_shared_blocks=1,
        attn_scale=32 ** -0.5, d_inner_mult=2, sliding_window=4096,
        ssm_chunk=256,
    )


def smoke() -> ArchConfig:
    return config().replace(
        n_layers=4, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        vocab_size=512, d_ff=256, ssm_state=16, ssm_heads=4,
        hybrid_layer_ids=(1, 3), attn_scale=16 ** -0.5,
        ssm_chunk=16, sliding_window=16, dtype="float32")
