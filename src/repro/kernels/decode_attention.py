"""Single-token GQA decode attention Pallas kernel (the decode_32k /
long_500k hot spot).

One new query token per sequence attends over a long, padded KV cache.
Tiling: grid = (batch, kv_blocks); each step loads a (block_k, Hkv, head_dim)
KV tile spanning every kv head -- Mosaic tiles the last two block dims, and
(Hkv, head_dim) is the whole array there, where a one-head (1, head_dim)
slice is refused.  Per kv head it updates fp32 online-softmax accumulators
for the whole GQA *group* of queries at once ((group, d) tile), so the MXU
sees a (group x block_k) matmul instead of a vector dot.  The cache is read
in the model's own (B,S,Hkv,D) layout, once per step.  Valid cache lengths
arrive via scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_KV_TILE_BYTES = 8 << 20   # of the 16 MiB of scoped VMEM a v5e kernel may use


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, n_kv_heads: int):
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for h in range(n_kv_heads):
        q = q_ref[h].astype(jnp.float32) * scale              # (group, d)
        k = k_ref[:, h, :].astype(jnp.float32)                # (bk, d)
        s = q @ k.T                                           # (group, bk)
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < len_ref[ib], s, NEG_INF)

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1)
        acc_ref[h] = acc_ref[h] * alpha[:, None] + p @ v_ref[:, h, :].astype(jnp.float32)
        m_ref[h] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool = True) -> jax.Array:
    """q: (B,Hq,D); caches: (B,S,Hkv,D); cache_len: (B,) int32 -> (B,Hq,D)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = max(hq // hkv, 1)
    scale_ = scale if scale is not None else d ** -0.5
    # K and V tiles span every kv head and are double-buffered: cap the
    # block so the four (bk, Hkv, D) buffers stay within _KV_TILE_BYTES of
    # VMEM.  A VMEM tile is 8 sublanes of 32 bits by 128 lanes, so Hkv pads
    # up to 8 rows (16 for bf16) and D up to 128.
    item = k_cache.dtype.itemsize
    sub = 8 * 4 // item
    row_bytes = -(-hkv // sub) * sub * (-(-d // 128) * 128) * item
    bk = min(block_k, s, max(8, _KV_TILE_BYTES // (4 * row_bytes) // 8 * 8))
    pad_k = (-s) % bk
    if pad_k:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nk = k_cache.shape[1] // bk
    qg = q.reshape(b, hkv, group, d)
    kernel = functools.partial(_decode_kernel, scale=scale_, block_k=bk,
                               n_kv_heads=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((None, hkv, group, d), lambda ib, ik, lens: (ib, 0, 0, 0)),
            pl.BlockSpec((None, bk, hkv, d), lambda ib, ik, lens: (ib, ik, 0, 0)),
            pl.BlockSpec((None, bk, hkv, d), lambda ib, ik, lens: (ib, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, hkv, group, d), lambda ib, ik, lens: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group), jnp.float32),
            pltpu.VMEM((hkv, group), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, hq, d)
