"""Chunked mLSTM (xLSTM matrix-memory) Pallas kernel.

Same TPU mapping as ssm_scan: grid = (batch, heads, chunks) with chunks
innermost/sequential; the stabilised (C, n, m) carry lives in VMEM scratch;
within a chunk the recurrence becomes (T,T)/(T,D) MXU matmuls.  Matches
kernels.ref.mlstm_scan_ref (y is stabiliser-invariant) and the jnp twin
models/ssm._mlstm_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, y_ref,
                  c_ref, n_ref, m_ref, *, chunk: int, d: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    qs = q_ref[...].astype(jnp.float32) * (d ** -0.5)            # (T,D)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    li = li_ref[...].astype(jnp.float32)                          # (1,T)
    lf = lf_ref[...].astype(jnp.float32)
    C, nv, m = c_ref[...], n_ref[...], m_ref[...]                 # (D,D),(1,D),(1,1)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal, diag = t_idx >= s_idx, t_idx == s_idx
    # cumsum of the log forget gates as masked reductions (exact f32 adds):
    # (T,1) columns index rows t, (1,T) rows index columns s
    bcum = jnp.sum(jnp.where(causal, lf, 0.0), axis=1, keepdims=True)      # (T,1)
    bcum_row = jnp.sum(jnp.where(diag, bcum, 0.0), axis=0, keepdims=True)  # (1,T)
    li_col = jnp.sum(jnp.where(diag, li, 0.0), axis=1, keepdims=True)      # (T,1)
    b_last = jnp.sum(lf, axis=1, keepdims=True)                            # (1,1)
    wlog = jnp.where(causal, bcum - bcum_row + li, NEG)           # (T,T)
    glog = bcum + m                                               # (T,1)
    m_row = jnp.maximum(jnp.max(wlog, axis=1, keepdims=True), glog)
    wexp = jnp.exp(wlog - m_row)
    gexp = jnp.exp(glog - m_row)

    scores = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ()))) * wexp  # (T,T)
    y_intra = scores @ v
    y_state = gexp * jax.lax.dot_general(
        qs, C, (((1,), (1,)), ((), ())))                          # C[d,e]: q over e
    nq = jnp.sum(scores, axis=1, keepdims=True) \
        + gexp * jnp.sum(qs * nv, axis=1, keepdims=True)          # (T,1)
    denom = jnp.maximum(jnp.abs(nq), jnp.exp(-m_row))
    y_ref[...] = ((y_intra + y_state) / denom).astype(y_ref.dtype)

    # carry update, restabilised at m_new
    m_new = jnp.maximum(b_last + m, jnp.max(li + (b_last - bcum_row), axis=1,
                                            keepdims=True))
    c_decay = jnp.exp(b_last + m - m_new)                         # (1,1)
    inj = jnp.exp(li_col + (b_last - bcum) - m_new)               # (T,1)
    # Mosaic cannot broadcast (1,1) over sublanes and lanes in one step:
    # widen to a (D,1) column through a select, which is not folded away
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
    c_decay_col = jnp.where(rows >= 0, c_decay, 0.0)              # (D,1)
    c_ref[...] = C * c_decay_col + (v * inj).T @ k
    n_ref[...] = nv * c_decay + jnp.sum(k * inj, axis=0, keepdims=True)
    m_ref[...] = m_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, logi, logf, *, chunk: int = 128, interpret: bool = True):
    """q,k,v: (B,S,H,D); logi/logf: (B,S,H) (log-space gates). -> y (B,S,H,D)."""
    b, s, h, d = q.shape
    t = min(chunk, s)
    pad = (-s) % t
    if pad:
        zp4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, zp4) for a in (q, k, v))
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)), constant_values=NEG)
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))
    nc = q.shape[1] // t
    # head-major, so that each grid step's tiles are (T, D) and (1, T)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))      # (B,H,S,D)
    logi, logf = (a.transpose(0, 2, 1)[:, :, None, :] for a in (logi, logf))
    kernel = functools.partial(_mlstm_kernel, chunk=t, d=d)
    tile = pl.BlockSpec((None, None, t, d), lambda ib, ih, ic: (ib, ih, ic, 0))
    gate = pl.BlockSpec((None, None, 1, t), lambda ib, ih, ic: (ib, ih, 0, ic))
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[tile, tile, tile, gate, gate],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, logi, logf).transpose(0, 2, 1, 3)
    return y[:, :s] if pad else y
