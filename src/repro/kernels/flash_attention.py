"""Blocked (flash-style) causal attention Pallas kernel with GQA + sliding
window, for prefill / training.

TPU mapping: grid = (batch, q_heads, q_blocks, kv_blocks) with the kv axis
innermost -- TPU executes the grid sequentially, so fp32 online-softmax
accumulators live in VMEM scratch and persist across kv steps.  Block sizes
default to 128x128 (MXU-aligned); q/k/v tiles are (block, head_dim) in VMEM.
The kernel runs head-major, (B,H,S,D): Mosaic tiles the last two block dims
(multiples of (8, 128) or the whole dim), so the per-head tile must be
(seq_block, head_dim) and not (1, head_dim) as the model's (B,S,H,D) layout
would give.  GQA is handled in the BlockSpec index_map (kv head = q head //
group).  Padded kv positions (when Skv % block_k != 0) are masked via kv_len.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, kv_len: int,
                  block_q: int, block_k: int, n_kv_blocks: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale                 # (bq, d)
    k = k_ref[...].astype(jnp.float32)                         # (bk, d)
    s = q @ k.T                                                # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v_ref[...].astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D). Returns (B,Sq,Hq,D)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = max(hq // hkv, 1)
    scale_ = scale if scale is not None else d ** -0.5
    bq, bk = min(block_q, sq), min(block_k, skv)
    pad_q, pad_k = (-sq) % bq, (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // bq, k.shape[1] // bk
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))   # head-major
    kernel = functools.partial(
        _flash_kernel, scale=scale_, causal=causal, window=window, kv_len=skv,
        block_q=bq, block_k=bk, n_kv_blocks=nk, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v).transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :sq]
    return out
