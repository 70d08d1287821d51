"""Chunked SSD (Mamba2) scan Pallas kernel, used by the xlstm/zamba2 paths.

TPU adaptation of the GPU SSD algorithm: instead of warp-level prefix scans,
the sequence is blocked into VMEM-resident chunks of length T; within a
chunk the recurrence is re-expressed as dense (T x T) / (T x N) matmuls (MXU
work), and the (P x N) state is carried across chunks in VMEM scratch --
grid = (batch, heads, chunks) with chunks innermost/sequential.

Math per chunk (a_t = exp(A*dt_t), cum_t = cumsum(log a)):
  y_t = exp(cum_t) * (C_t . h_in) + sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t.B_s) x_s
  h_out = exp(cum_T) h_in + sum_s exp(cum_T - cum_s) dt_s (x_s outer B_s)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                chunk: int, n_chunks: int):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)               # (T, P)
    dt_row = dt_ref[...].astype(jnp.float32)         # (1, T)
    a = a_ref[ih]                                    # scalar decay rate (<0)
    bm = b_ref[...].astype(jnp.float32)              # (T, N)
    cm = c_ref[...].astype(jnp.float32)              # (T, N)
    h = h_ref[...]                                   # (P, N) f32 carry

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal, diag = t_idx >= s_idx, t_idx == s_idx
    # cumsum as masked reductions (exact f32 adds), in both orientations:
    # a (T,1) column indexes rows t, a (1,T) row indexes columns s
    log_a = a * dt_row                                            # (1, T)
    cum = jnp.sum(jnp.where(causal, log_a, 0.0), axis=1, keepdims=True)   # (T, 1)
    cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0, keepdims=True)   # (1, T)
    dt_col = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1, keepdims=True)  # (T, 1)
    cum_last = jnp.sum(log_a)
    # L[t,s] = exp(cum_t - cum_s) for s<=t else 0 (mask exponent pre-exp to
    # avoid overflow in the dead upper triangle)
    L = jnp.exp(jnp.where(causal, cum - cum_row, -1e30))
    G = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))    # (T, T)
    M = G * L * dt_row
    y_intra = M @ x                                              # (T, P)
    y_state = jnp.exp(cum) * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())))                         # (T, P)
    y_ref[...] = (y_intra + y_state).astype(y_ref.dtype)

    w = dt_col * jnp.exp(cum_last - cum)                         # (T, 1)
    h_new = h * jnp.exp(cum_last) + (x * w).T @ bm               # (P, N)
    h_ref[...] = h_new

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hout_ref[...] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256, interpret: bool = True):
    """Chunked SSD scan.

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N).
    Returns (y (B,S,H,P), h_final (B,H,P,N) fp32).
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    t = min(chunk, s)
    pad = (-s) % t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> identity steps
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // t
    # head-major: each grid step's tiles are (T, P) and (1, T), which Mosaic
    # accepts; the model's (B,S,H,P) layout would give (1, P) head slices
    xt = x.transpose(0, 2, 1, 3)                      # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)[:, :, None, :]        # (B,H,1,S)
    kernel = functools.partial(_ssd_kernel, chunk=t, n_chunks=nc)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, t, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((None, None, 1, t), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, t, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((None, t, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, t, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((None, None, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bm, Cm)
    y = y.transpose(0, 2, 1, 3)
    if pad:
        y = y[:, :s]
    return y, h_final
