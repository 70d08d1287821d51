"""Attention variants: GQA (+RoPE / M-RoPE / sliding window), MLA (deepseek),
cross-attention (whisper).  Pure functions over param dicts.

Decode ("serve_step") semantics: ONE new token per sequence against a KV
cache of length cfg.max_decode_len.  Sliding-window ("local") layers use a
ring-buffer cache of size min(window, max_decode_len) -- correct because
post-RoPE attention is permutation-invariant over keys, so ring order does
not matter once positions are baked in at write time.

MLA keeps the *compressed* cache (c_kv, k_rope) and decodes in the absorbed
form (q folded through w_uk / output through w_uv) -- the memory win the
paper's MLA citation exists for.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels import ops
from . import modules as nn
from .sharding import constrain

Params = Any


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32) / (head_dim // 2))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B,S,H,D); positions: (B,S) -> rotated x (half-split convention)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                   # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (B,S,D/2)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Qwen2-VL M-RoPE: positions (B,3,S) = (t,h,w) streams; the rotary
    frequency dims are split into 3 sections, one per stream."""
    d = x.shape[-1]
    half = d // 2
    s1 = half - 2 * (half // 3)
    sections = [s1, half // 3, half // 3]
    freqs = rope_freqs(d, theta)
    pos_f = positions.astype(jnp.float32)                          # (B,3,S)
    parts, start = [], 0
    for i, sec in enumerate(sections):
        ang = pos_f[:, i, :, None] * freqs[start:start + sec]      # (B,S,sec)
        parts.append(ang)
        start += sec
    angles = jnp.concatenate(parts, axis=-1)                       # (B,S,D/2)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _rope(x, positions, cfg: ArchConfig):
    if not cfg.use_rope:
        return x
    if cfg.use_mrope:
        return apply_mrope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def _tpos(positions, cfg: ArchConfig):
    """Temporal (1D) position stream -- for cache indexing under M-RoPE."""
    return positions[:, 0] if cfg.use_mrope else positions


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def gqa_init(key, cfg: ArchConfig, dtype, d_in: int = 0) -> Params:
    """Projections from d_in (default d_model) wide inputs back to d_model."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d_in = d_in or d
    k1, k2, k3, k4 = nn.split_keys(key, 4)
    return {
        "wq": nn.dense_init(k1, (d_in, hq, hd), fan_in=d_in, dtype=dtype),
        "wk": nn.dense_init(k2, (d_in, hkv, hd), fan_in=d_in, dtype=dtype),
        "wv": nn.dense_init(k3, (d_in, hkv, hd), fan_in=d_in, dtype=dtype),
        "wo": nn.dense_init(k4, (hq, hd, d), fan_in=hq * hd, dtype=dtype),
    }


def softmax_scale(cfg: ArchConfig) -> float:
    return cfg.attn_scale or cfg.head_dim ** -0.5


LANES = 128     # the TPU's vector lanes


def kv_width(cfg: ArchConfig) -> int:
    """Width of one head's row in the K/V cache: the head dim rounded up to
    a multiple of the 128 lanes.  The TPU lays a cache whose last axis is
    so aligned out row-major, the layout the decode step's in-place row
    write keeps.  At any other head dim (64, 80, 96, 120 and 224 compiled
    for a v5e) it puts the sequence axis minor, and the step relayouts the
    whole stack into and out of its scan every step, with two caches of
    temporaries.  The zeros are bytes held and read: the K/V cache grows
    by 6.7% at 120, 14.3% at 224 and 100% at 64, where the unpadded step's
    temporaries would still peak higher.  q, k and v are padded with them,
    which adds nothing to a score, and attention drops them from its
    output."""
    return -(-cfg.head_dim // LANES) * LANES


def to_kv_width(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last (head) axis of x to `width`."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def gqa_forward(p: Params, x: jax.Array, positions: jax.Array, cfg: ArchConfig,
                *, window: int = 0, causal: bool = True,
                return_kv: bool = False):
    """Full-sequence attention (train / prefill). x: (B,S,D)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = constrain(_rope(q, positions, cfg), "batch", None, "model")
    k = constrain(_rope(k, positions, cfg), "batch", None, "model")
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            scale=softmax_scale(cfg),
                            use_kernel=cfg.use_kernels,
                            chunked=cfg.fused_attention,
                            chunk_k=cfg.attn_chunk,
                            unroll=cfg.scan_unroll if cfg.chunk_unroll is None
                            else cfg.chunk_unroll)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    out = constrain(out, "batch", None, None)
    if return_kv:
        return out, (k, v)
    return out


def gqa_decode(p: Params, x: jax.Array, stack: dict, g, positions: jax.Array,
               cfg: ArchConfig, *, window: int = 0):
    """One-token decode against layer g of a layer-stacked cache.

    x: (B,1,D); stack {k,v: (G,B,S,Hkv,w)}, w = kv_width; positions (B,) or (B,3)
    absolute positions of the new token.  Writes the token's K/V row into
    layer g (at its ring slot when windowed) and attends over that layer.
    Returns (out, the stack with the row written)."""
    b = x.shape[0]
    # positions for rope helpers expect (B,S) or (B,3,S)
    pos_seq = positions[:, None] if not cfg.use_mrope else positions[:, :, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = _rope(q, pos_seq, cfg)[:, 0]                               # (B,Hq,hd)
    k = _rope(k, pos_seq, cfg)[:, 0]                               # (B,Hkv,hd)
    v = v[:, 0]
    tpos = _tpos(pos_seq, cfg)[:, 0]                               # (B,) int
    cache_size, width = stack["k"].shape[2], stack["k"].shape[-1]
    if window > 0:                      # ring-buffer cache for local layers
        size = min(window, cache_size)
        slot = tpos % size
        eff_len = jnp.minimum(tpos + 1, size)
    else:
        slot = tpos
        eff_len = tpos + 1
    bidx = jnp.arange(b)
    k_st = stack["k"].at[g, bidx, slot].set(to_kv_width(k, width).astype(stack["k"].dtype))
    v_st = stack["v"].at[g, bidx, slot].set(to_kv_width(v, width).astype(stack["v"].dtype))
    o = ops.decode_attention(to_kv_width(q, width), k_st[g], v_st[g],
                             eff_len.astype(jnp.int32), scale=softmax_scale(cfg),
                             use_kernel=cfg.use_kernels)[..., :cfg.head_dim]
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None, :]
    return out, {"k": k_st, "v": v_st}


def gqa_prefill(p: Params, x: jax.Array, cache: dict, positions: jax.Array,
                cfg: ArchConfig, *, window: int = 0):
    """Chunked prefill: C prompt tokens at once against the decode cache.

    x: (B,C,D); positions: (B,C) (or (B,3,C) M-RoPE) absolute, contiguous
    ascending; cache {k,v:(B,S,Hkv,w)}, w = kv_width.  Writes the chunk's K/V rows into
    the cache and attends every query with the same grouped masked softmax
    the one-token decode path (`gqa_decode` -> decode_attention_ref) uses, so a
    P-token prompt costs O(P/C) calls instead of P decode steps while
    producing decode-identical logits: rows past a query's position differ
    (written here, zero in decode) but are masked to the same exact NEG_INF
    before the softmax.  Windowed layers attend over the same keys in
    another order, so there the softmax sums may differ in the last bits.
    Returns (out (B,C,D), new_cache)."""
    b, c, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    cache_size, width = cache["k"].shape[1], cache["k"].shape[-1]
    q = to_kv_width(_rope(q, positions, cfg), width)               # (B,C,Hq,w)
    k = to_kv_width(_rope(k, positions, cfg), width)               # (B,C,Hkv,w)
    tpos = _tpos(positions, cfg)                                   # (B,C) int
    k_cd = k.astype(cache["k"].dtype)
    v_cd = to_kv_width(v, width).astype(cache["v"].dtype)

    if window > 0:
        # Ring buffer of `size` slots.  At query position t the decode step
        # sees exactly the positions in (t - size, t] that are >= 0: the
        # pre-chunk ring rows the chunk has not yet overwritten, plus the
        # chunk's own rows up to t.  Every query attends over the shared key
        # set [ring ; chunk] under that mask, so the scores are C x (size+C)
        # and no per-query copy of the ring is built (at size 2048, C 256
        # and 32 heads that copy was 7.5 GB).
        size = min(window, cache_size)
        slots = jnp.arange(size)
        start = tpos[:, :1]                                        # chunk offset
        # position each slot held before the chunk (< 0: never written)
        held = (start - 1) - ((start - 1 - slots[None, :]) % size)   # (B,size)
        kpos = jnp.concatenate([held, tpos], axis=1)               # (B,size+C)
        keys = jnp.concatenate([cache["k"], k_cd], axis=1)         # (B,size+C,Hkv,w)
        vals = jnp.concatenate([cache["v"], v_cd], axis=1)
        kq = kpos[:, None, :]
        tq = tpos[:, :, None]
        valid = (kq >= 0) & (kq <= tq) & (kq > tq - size)          # (B,C,size+C)
        o = _grouped_attend(q, keys, vals, valid, softmax_scale(cfg))
        # final ring state: per slot, the last chunk position that maps there
        # (deterministic gather -- scatter with duplicate ring indices is not)
        last = tpos[:, -1:]
        cand_f = last - ((last - slots[None, :]) % size)           # (B,size)
        sel_f = (cand_f >= start)[..., None, None]
        idx_f = jnp.clip(cand_f - start, 0, c - 1)
        b2 = jnp.arange(b)[:, None]
        k_cache = jnp.where(sel_f, k_cd[b2, idx_f], cache["k"])
        v_cache = jnp.where(sel_f, v_cd[b2, idx_f], cache["v"])
    else:
        b2 = jnp.arange(b)[:, None]
        k_cache = cache["k"].at[b2, tpos].set(k_cd)
        v_cache = cache["v"].at[b2, tpos].set(v_cd)
        valid = jnp.arange(cache_size)[None, None, :] < (tpos[:, :, None] + 1)
        o = _grouped_attend(q, k_cache, v_cache, valid, softmax_scale(cfg))
    out = jnp.einsum("bshk,hkd->bsd", o[..., :cfg.head_dim], p["wo"])
    return out, {"k": k_cache, "v": v_cache}


def _grouped_attend(q: jax.Array, keys: jax.Array, vals: jax.Array,
                    valid: jax.Array, scale: float) -> jax.Array:
    """q (B,C,Hq,hd) against keys/vals (B,K,Hkv,hd) under valid (B,C,K):
    decode_attention_ref's grouped contraction for C queries at once (query
    head h reads KV head h // G), with no per-query-head copy of the keys."""
    b, c, hq, hd = q.shape
    hkv = keys.shape[2]
    qg = q.astype(keys.dtype).reshape(b, c, hkv, hq // hkv, hd)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg, keys,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", probs.astype(vals.dtype), vals,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, c, hq, hd).astype(q.dtype)


def gqa_cache_shape(cfg: ArchConfig, batch: int, length: int, window: int = 0):
    size = min(window, length) if window > 0 else length
    kv = (batch, size, cfg.n_kv_heads, kv_width(cfg))
    return {"k": kv, "v": kv}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------
def cross_init(key, cfg: ArchConfig, dtype) -> Params:
    return gqa_init(key, cfg, dtype)


def cross_forward(p: Params, x: jax.Array, enc_kv: tuple, cfg: ArchConfig):
    """x: (B,S,D); enc_kv = (k,v) precomputed from encoder output."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = enc_kv
    o = ops.flash_attention(q, k, v, causal=False, use_kernel=cfg.use_kernels)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def cross_kv(p: Params, enc_out: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return k, v


def cross_decode(p: Params, x: jax.Array, enc_kv: tuple, cfg: ArchConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])[:, 0]              # (B,H,hd)
    k, v = enc_kv
    lens = jnp.full((x.shape[0],), k.shape[1], jnp.int32)
    o = ops.decode_attention(q, k, v, lens, use_kernel=cfg.use_kernels)
    return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None, :]


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank compressed KV + decoupled RoPE
# ---------------------------------------------------------------------------
def mla_init(key, cfg: ArchConfig, dtype) -> Params:
    d, hq = cfg.d_model, cfg.n_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    ks = nn.split_keys(key, 6)
    return {
        "w_uq": nn.dense_init(ks[0], (d, hq, dn), fan_in=d, dtype=dtype),
        "w_qr": nn.dense_init(ks[1], (d, hq, dr), fan_in=d, dtype=dtype),
        "w_dkv": nn.dense_init(ks[2], (d, r + dr), fan_in=d, dtype=dtype),
        "w_uk": nn.dense_init(ks[3], (r, hq, dn), fan_in=r, dtype=dtype),
        "w_uv": nn.dense_init(ks[4], (r, hq, dv), fan_in=r, dtype=dtype),
        "wo": nn.dense_init(ks[5], (hq, dv, d), fan_in=hq * dv, dtype=dtype),
    }


def _mla_scale(cfg: ArchConfig) -> float:
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def mla_forward(p: Params, x: jax.Array, positions: jax.Array, cfg: ArchConfig,
                *, return_cache: bool = False):
    """Prefill/train: decompress to MHA and run flash attention."""
    r = cfg.kv_lora_rank
    c = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])                   # (B,S,r+dr)
    c_kv, k_rope = c[..., :r], c[..., r:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,dr)
    q_nope = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"])
    q_rope = apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["w_qr"]), positions,
                        cfg.rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    hq = cfg.n_heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    q = constrain(q, "batch", None, "model")
    # v head dim differs from qk dim -> pad v for the fused kernel path, or
    # use the reference path which supports it natively.
    dqk, dv = q.shape[-1], v.shape[-1]
    if cfg.use_kernels and dv != dqk:
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dqk - dv)))
        o = ops.flash_attention(q, k, v_p, causal=True, scale=_mla_scale(cfg),
                                use_kernel=True)[..., :dv]
    else:
        o = ops.flash_attention(q, k, v, causal=True, use_kernel=False,
                                chunked=cfg.fused_attention,
                                chunk_k=cfg.attn_chunk,
                                unroll=cfg.scan_unroll if cfg.chunk_unroll is None
                                else cfg.chunk_unroll)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if return_cache:
        return out, {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}
    return out


def mla_decode(p: Params, x: jax.Array, stack: dict, g, positions: jax.Array,
               cfg: ArchConfig):
    """Absorbed-form decode against layer g of the stacked compressed cache.

    stack: {c_kv: (G,B,S,r), k_rope: (G,B,S,dr)}; positions: (B,) absolute.
    Writes the token's c_kv / k_rope rows into layer g and attends over that
    layer.  Returns (out, the stack with the rows written)."""
    b = x.shape[0]
    r = cfg.kv_lora_rank
    pos_seq = positions[:, None]
    c = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_new, krope_new = c[..., :r][:, 0], c[..., r:]
    krope_new = apply_rope(krope_new[:, :, None, :], pos_seq, cfg.rope_theta)[:, 0, 0]
    bidx = jnp.arange(b)
    ckv_st = stack["c_kv"].at[g, bidx, positions].set(
        c_new.astype(stack["c_kv"].dtype))
    krope_st = stack["k_rope"].at[g, bidx, positions].set(
        krope_new.astype(stack["k_rope"].dtype))
    c_kv, k_rope = ckv_st[g], krope_st[g]

    q_nope = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"])[:, 0]       # (B,H,dn)
    q_rope = apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["w_qr"]), pos_seq,
                        cfg.rope_theta)[:, 0]                      # (B,H,dr)
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope, p["w_uk"])          # absorbed q
    # scores over the compressed cache: native-dtype dots + f32 accumulation
    # (an .astype(f32) here would materialise an f32 copy of the WHOLE cache
    # per layer -- the dominant byte term of the decode baseline, §Perf C1)
    logits = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(c_kv.dtype), c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhk,bsk->bhs", q_rope.astype(k_rope.dtype), k_rope,
                           preferred_element_type=jnp.float32)) * _mla_scale(cfg)
    valid = jnp.arange(c_kv.shape[1])[None, :] <= positions[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o_c = jnp.einsum("bhs,bsr->bhr", probs.astype(c_kv.dtype), c_kv,
                     preferred_element_type=jnp.float32)               # (B,H,r)
    o = jnp.einsum("bhr,rhk->bhk", o_c.astype(x.dtype), p["w_uv"])     # (B,H,dv)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None, :]
    return out, {"c_kv": ckv_st, "k_rope": krope_st}


def mla_prefill(p: Params, x: jax.Array, cache: dict, positions: jax.Array,
                cfg: ArchConfig):
    """Chunked prefill in the absorbed form over the compressed cache.

    x: (B,C,D); positions: (B,C) absolute, contiguous ascending.  Decode
    twin of `mla_decode`: writes the chunk's compressed rows, then runs the
    same absorbed-einsum masked softmax for all C queries at once."""
    b, c = x.shape[:2]
    r = cfg.kv_lora_rank
    cc = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])                  # (B,C,r+dr)
    c_new, krope_new = cc[..., :r], cc[..., r:]
    krope_new = apply_rope(krope_new[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    b2 = jnp.arange(b)[:, None]
    c_kv = cache["c_kv"].at[b2, positions].set(c_new.astype(cache["c_kv"].dtype))
    k_rope = cache["k_rope"].at[b2, positions].set(krope_new.astype(cache["k_rope"].dtype))

    q_nope = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"])             # (B,C,H,dn)
    q_rope = apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["w_qr"]), positions,
                        cfg.rope_theta)                            # (B,C,H,dr)
    q_abs = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["w_uk"])
    logits = (jnp.einsum("bqhr,bsr->bqhs", q_abs.astype(c_kv.dtype), c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhk,bsk->bqhs", q_rope.astype(k_rope.dtype), k_rope,
                           preferred_element_type=jnp.float32)) * _mla_scale(cfg)
    valid = jnp.arange(c_kv.shape[1])[None, None, :] <= positions[:, :, None]
    logits = jnp.where(valid[:, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o_c = jnp.einsum("bqhs,bsr->bqhr", probs.astype(c_kv.dtype), c_kv,
                     preferred_element_type=jnp.float32)
    o = jnp.einsum("bqhr,rhk->bqhk", o_c.astype(x.dtype), p["w_uv"])
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_cache_shape(cfg: ArchConfig, batch: int, length: int):
    return {"c_kv": (batch, length, cfg.kv_lora_rank),
            "k_rope": (batch, length, cfg.qk_rope_dim)}
