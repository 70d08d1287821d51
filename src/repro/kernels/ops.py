"""Jit'd dispatch layer: Pallas kernel or pure-jnp reference.

Model code calls these entry points only; ``use_kernel`` comes from
ArchConfig.use_kernels, which no config turns on, so the served path runs
the jnp references.  With ``use_kernel=True`` a kernel compiles to Mosaic on
a TPU backend and runs in interpret mode (the kernel body executed op by op,
slowly) on any other backend, which is how the CPU tests sweep it for
correctness.  tests/test_tpu_compile.py compiles every kernel for a
described TPU v5e at real widths.
"""
from __future__ import annotations

import jax

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .rmsnorm import rmsnorm as _rmsnorm_pallas
from .ssm_scan import ssm_scan as _ssm_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def rmsnorm(x, scale, *, eps: float = 1e-6, use_kernel: bool = False):
    if use_kernel:
        return _rmsnorm_pallas(x, scale, eps=eps, interpret=_interpret())
    return ref.rmsnorm_ref(x, scale, eps)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    scale=None, use_kernel: bool = False, block_q=128,
                    block_k=128, chunked: bool = False, chunk_k: int = 1024,
                    unroll: bool = False):
    if use_kernel:
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset, block_q=block_q,
                             block_k=block_k, interpret=_interpret())
    if chunked:
        return flash_chunked_jnp(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, scale=scale,
                                 chunk_k=chunk_k, unroll=unroll)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale, q_offset=q_offset)


def flash_chunked_jnp(q, k, v, *, causal=True, window=0, q_offset=0,
                      scale=None, chunk_k: int = 1024, unroll: bool = False):
    """Online-softmax attention, lax.scan over KV chunks -- the jnp twin of
    the Pallas flash kernel: the (Sq, Skv) score matrix never exists as a
    whole, so HBM traffic stays O(S*D) instead of O(S^2).  Used as the
    'fused attention' model path for dry-run perf variants (on TPU the
    Pallas kernel takes over)."""
    import jax.numpy as jnp

    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]            # MLA: v head dim may differ from qk dim
    group = max(hq // hkv, 1)
    t = min(chunk_k, skv)
    pad = (-skv) % t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = k.shape[1] // t
    qf = q.astype(jnp.float32) * (scale if scale is not None else d ** -0.5)
    qpos = jnp.arange(sq)[:, None] + q_offset                     # (Sq, 1)

    def kv_step(carry, ic):
        acc, m, l = carry                                          # fp32
        # dynamic_slice per chunk: a (B,nc,t,H,D) pre-reshape overflows the
        # 2^31 element limit for 32k x 16-head x 128 tensors
        kb = jax.lax.dynamic_slice_in_dim(k, ic * t, t, axis=1)    # (B,t,Hkv,D)
        vb = jax.lax.dynamic_slice_in_dim(v, ic * t, t, axis=1)
        kb = jnp.repeat(kb.astype(jnp.float32), group, axis=2)
        vb = jnp.repeat(vb.astype(jnp.float32), group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)                  # (B,Hq,Sq,t)
        kpos = ic * t + jnp.arange(t)[None, :]                     # (1, t)
        mask = kpos < skv
        if causal:
            mask = mask & (kpos <= qpos)
        if window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (acc, m_new, l), None

    init = (jnp.zeros((b, hq, sq, dv), jnp.float32),
            jnp.full((b, hq, sq), -1e30, jnp.float32),
            jnp.zeros((b, hq, sq), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(kv_step, init, jnp.arange(nc),
                                  unroll=True if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                     use_kernel: bool = False, block_k=512):
    if use_kernel:
        return _decode_pallas(q, k_cache, v_cache, cache_len, scale=scale,
                              block_k=block_k, interpret=_interpret())
    return ref.decode_attention_ref(q, k_cache, v_cache, cache_len, scale=scale)


def ssm_scan(x, dt, A, Bm, Cm, *, chunk=256, h0=None, use_kernel: bool = False,
             unroll: bool = False):
    """Returns (y, h_final) from the state h0 (None: zero).  Bm/Cm are
    (B,S,N), one group, or (B,S,G,N), head h reading group h // (H // G).
    Reference path uses the chunked jnp algorithm (same math as the
    kernel), itself validated against the sequential oracle in tests.  The
    kernel starts from a zero state, so a carried state takes the jnp
    path; it takes one B/C group."""
    if not use_kernel or h0 is not None:
        return ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=chunk, h0=h0, unroll=unroll)
    if Bm.ndim == 4 and Bm.shape[2] > 1:
        raise ValueError("the Pallas SSD kernel takes one B/C group")
    if Bm.ndim == 4:
        Bm, Cm = Bm[:, :, 0], Cm[:, :, 0]
    return _ssm_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=_interpret())


def ssd_chunked_jnp(x, dt, A, Bm, Cm, *, chunk=256, h0=None, unroll: bool = False):
    """Chunked SSD in pure jnp (lax.scan over chunks) -- compact HLO for the
    512-device dry-run (one while-loop instead of S sequential steps).
    x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) or (B,S,G,N) with head h
    in group h // (H // G); h0 (B,H,P,N) the state before the first token."""
    import jax.numpy as jnp

    b, s, h, p = x.shape
    if Bm.ndim == 3:
        Bm, Cm = Bm[:, :, None], Cm[:, :, None]
    g, n = Bm.shape[2], Bm.shape[3]
    hg = h // g
    t = min(chunk, s)
    pad = (-s) % t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = x.shape[1] // t
    xf = x.astype(jnp.float32).reshape(b, nc, t, g, hg, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, t, g, hg)
    Bf = Bm.astype(jnp.float32).reshape(b, nc, t, g, n)
    Cf = Cm.astype(jnp.float32).reshape(b, nc, t, g, n)
    Af = A.astype(jnp.float32).reshape(g, hg)
    tri = jnp.tril(jnp.ones((t, t), jnp.float32))

    def chunk_step(hprev, args):
        # (B,t,G,Hg,P), (B,t,G,Hg), (B,t,G,N), (B,t,G,N); hprev (B,G,Hg,P,N)
        xc, dtc, bc, cc = args
        log_a = Af[None, None] * dtc                 # (B,t,G,Hg)
        cum = jnp.cumsum(log_a, axis=1)
        # mask the exponent BEFORE exp: upper-triangle cum_t-cum_s is large
        # positive (cum decreasing) and exp overflows -> inf*0 = NaN
        delta = jnp.where(tri[None, :, :, None, None] > 0,
                          cum[:, :, None] - cum[:, None, :], -1e30)
        L = jnp.exp(delta)                           # (B,t,s,G,Hg)
        G = jnp.einsum("btgn,bsgn->btsg", cc, bc)    # (B,t,s,G)
        M = G[..., None] * L * dtc[:, None]          # (B,t,s,G,Hg)
        y_intra = jnp.einsum("btsgh,bsghp->btghp", M, xc)
        y_state = jnp.exp(cum)[..., None] * jnp.einsum("btgn,bghpn->btghp", cc, hprev)
        w = dtc * jnp.exp(cum[:, -1:] - cum)         # (B,t,G,Hg)
        h_new = hprev * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "btghp,btgn,btgh->bghpn", xc, bc, w)
        return h_new, y_intra + y_state

    h0 = (jnp.zeros((b, g, hg, p, n), jnp.float32) if h0 is None
          else h0.astype(jnp.float32).reshape(b, g, hg, p, n))
    args = tuple(a.transpose(1, 0, *range(2, a.ndim)) for a in (xf, dtf, Bf, Cf))
    h_final, ys = jax.lax.scan(chunk_step, h0, args, unroll=True if unroll else 1)
    y = ys.transpose(1, 0, 2, 3, 4, 5).reshape(b, nc * t, h, p)[:, :s]
    return y.astype(x.dtype), h_final.reshape(b, h, p, n)
