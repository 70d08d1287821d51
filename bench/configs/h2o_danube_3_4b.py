"""h2o-danube-3-4b: the plain float32 reference, and the counts of work.

A Llama-style decoder (H2O-Danube3 report, arXiv:2407.09276; the published
config is named in h2o_danube_3_4b.json): RMSNorm, grouped-query attention
with rotary positions over a causal mask in every layer, a SwiGLU MLP, and
an untied output head.  Written from that description in plain jax.numpy, in
float32 with every matmul at HIGHEST precision, with no cache, no batching
of requests and no kernels.  It imports nothing of the program.

The weights are the ones the benchmark makes from the seed (`WEIGHTS` says
how each leaf is drawn).  Their tree is the serving program's checkpoint
layout: per layer, stacked on a leading axis, under phase0/slot0.  Norm
scales are stored as offsets from 1 (the norm multiplies by 1 + scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 512          # queries per block of the reference's attention

# leaf name -> (rank of one layer's leaf, the axes that make its fan-in;
# None: a norm scale, drawn with std 0.1 around 0)
WEIGHTS = {
    "embed": (2, (1,)), "lm_head": (2, (0,)), "final_norm": (1, None),
    "norm1": (1, None), "norm2": (1, None),
    "wq": (3, (0,)), "wk": (3, (0,)), "wv": (3, (0,)), "wo": (3, (0, 1)),
    "w_gate": (2, (0,)), "w_up": (2, (0,)), "w_down": (2, (0,)),
}


def _dims(conf):
    d, h, kv = conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return d, h, kv, hd, conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]


# ---------------------------------------------------------------------------
# Counts, from shapes
# ---------------------------------------------------------------------------
def matmul_params(conf) -> int:
    """Weights that multiply every token: attention, MLP and output head."""
    d, h, kv, hd, ff, v, n = _dims(conf)
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    return n * per_layer + d * v


def param_count(conf) -> int:
    d, h, kv, hd, ff, v, n = _dims(conf)
    return matmul_params(conf) + v * d + (2 * n + 1) * d


def flops_per_token(conf, position: int) -> float:
    """Forward FLOPs of the token at `position` (0-based): two per weight
    multiply-add, plus the scores and the weighted sum over the position+1
    positions it attends to."""
    d, h, kv, hd, ff, v, n = _dims(conf)
    return 2.0 * matmul_params(conf) + 4.0 * n * h * hd * (position + 1)


def decode_min_bytes(conf, contexts, weight_bytes: int = 2,
                     cache_bytes: int = 2) -> float:
    """The least HBM traffic of one decode step over sequences at the given
    context lengths (positions already in the cache): every weight read once,
    the embedding rows of the step's tokens, each sequence's live K/V rows
    read and its new row written."""
    d, h, kv, hd, ff, v, n = _dims(conf)
    row = n * 2 * kv * hd * cache_bytes                 # K and V, all layers
    weights = (matmul_params(conf) + len(contexts) * d) * weight_bytes
    kv_rows = sum(c + 1 for c in contexts) * row
    return float(weights + kv_rows)


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------
def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding: the control's precision."""
    s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(FP8).astype(F32) / s


def _mm(eq, a, b, fp8: bool):
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, pos, theta):
    """Rotary positions, half-split pairs. x (B,S,H,D), pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention, queries in blocks. q (B,S,H,D), k/v
    (B,S,KV,D) with query head h reading kv head h // (H // KV)."""
    b, s, h, dh = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    blk = min(Q_BLOCK, s)
    nb = s // blk
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        qpos = i * blk + jnp.arange(blk)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * dh ** -0.5
        ok = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nb))             # (nb,B,blk,H,D)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


def forward(w, conf, tokens, *, fp8: bool = False):
    """tokens (B,S) int32, S a multiple of Q_BLOCK or below it -> logits
    (B,S,V) float32.  With fp8, every matmul with a weight takes both its
    operands rounded to scaled float8: the control."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    pos = jnp.arange(tokens.shape[1])
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def layer(x, lw):
        a = lw["mixer"]
        hn = _norm(x, lw["norm1"], eps)
        q = _rope(_mm("bsd,dhk->bshk", hn, a["wq"], fp8), pos, theta)
        k = _rope(_mm("bsd,dhk->bshk", hn, a["wk"], fp8), pos, theta)
        v = _mm("bsd,dhk->bshk", hn, a["wv"], fp8)
        o = _attention(q, k, v)
        x = x + _mm("bshk,hkd->bsd", o, a["wo"], fp8)
        m = lw["ffn"]
        hn = _norm(x, lw["norm2"], eps)
        g = _mm("bsd,df->bsf", hn, m["w_gate"], fp8)
        u = _mm("bsd,df->bsf", hn, m["w_up"], fp8)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["phase0"]["slot0"])
    x = _norm(x, w["final_norm"], eps)
    return _mm("bsd,dv->bsv", x, w["lm_head"], fp8)
