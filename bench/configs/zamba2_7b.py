"""Zamba2-7B-Instruct: the plain float32 reference, and the counts of work.

A hybrid of Mamba2 layers and two weight-shared attention+MLP blocks
(Zamba2, arXiv:2411.15242; the published config is named in
zamba2_7b.json).  Written from the published layer equations in plain
jax.numpy, in float32 with every matmul at HIGHEST precision, with no
cache, no batching of requests and no kernels.  It imports nothing of the
program.

    Mamba2 layer l:  x <- x + Mamba2(RMSNorm(x + t_l))
    t_l (at hybrid_layer_ids[k], else 0), with block b = k mod num_mem_blocks:
        s = RMSNorm([x ; e])                     e: the token embedding
        a = o_proj(Attn(rope(q), rope(k), v))    causal, scale (hd / 2) ** -0.5
        h = RMSNorm(a);  [g ; u] = W_gu h + B_k A_k h
        t = W_k down(gelu(g) * u)                exact GELU, no residual
    Mamba2(u): z, xBC, dt = in_proj(u); xBC = silu(conv(xBC) + bias) -> x, B, C;
        dt = softplus(dt + dt_bias); head h reads group g = h // (H / G):
        h_t = exp(dt A_h) h_{t-1} + dt x_t B_g^T;  y_t = h_t C_g + D_h x_t
        out_proj(RMSNorm over each group of heads of y * silu(z))

The SSD recurrence is computed in its plain form: a masked quadratic form
within blocks of `chunk_size` positions, the state carried between blocks.
Logits come from the tied embedding.

The weights are the ones the benchmark makes from the seed (`WEIGHTS` says
how each leaf is drawn).  Their tree is the serving program's layout, read
here by position only: "embed", "final_norm"; "shared", the shared blocks
stacked on a leading axis; and per phase "phase<p>", whose leading axis is
its groups.  Phase 0 holds the layers before the first hybrid layer, one a
group ("slot0"); each later phase holds groups that start at a hybrid layer
and run to the next one ("slot0", "slot1", ...), with that invocation's
adapter and linear under "hybrid".  Norm scales are stored as offsets from
1 (the norm multiplies by 1 + scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 512          # queries per block of the reference's attention

# leaf name -> (rank of one layer's leaf, the axes whose sizes divide its
# std: std = 1 / sqrt(product of those sizes), so () draws std 1; None: a
# norm scale or a per-channel vector, drawn with std 0.1 around 0).
# The harness draws every leaf around 0.  Mamba2's own initialisation is
# as near as that allows: dt_proj's std is divided by sqrt(heads) more, so
# that a head's dt = softplus(dt_bias) hardly moves with the token (as
# under Mamba2's negative dt_bias, where softplus is flat); dt_bias and
# A_log at std 1 spread the heads' decay exp(-dt |A|) from about 0.99 to
# 0 a position, so that a few percent of heads carry state over tens to
# hundreds of positions; and D_skip at std 1 (Mamba2: 1) keeps the skip
# path beside the state's, which keeps the random 27-layer stage from
# amplifying bfloat16 rounding into a different argmax (PERF.md section 6).
WEIGHTS = {
    "embed": (2, (1,)), "final_norm": (1, None), "norm1": (1, None),
    "norm2": (1, None),
    # Mamba2
    "in_proj": (2, (0,)), "xbc_proj": (2, (0,)), "dt_proj": (2, (0, 1)),
    "conv_w": (2, (0,)), "conv_b": (1, None), "dt_bias": (1, ()),
    "A_log": (1, ()), "D_skip": (1, ()), "ssm_norm": (1, None),
    "out_proj": (2, (0,)),
    # shared blocks
    "wq": (3, (0,)), "wk": (3, (0,)), "wv": (3, (0,)), "wo": (3, (0, 1)),
    "w_gate": (2, (0,)), "w_up": (2, (0,)), "w_down": (2, (0,)),
    # per invocation
    "adapter_in": (2, (0,)), "adapter_gate": (2, (0,)), "adapter_up": (2, (0,)),
    "linear": (2, (0,)),
}


def _dims(conf):
    d = conf["hidden_size"]
    return {
        "d": d, "inner": conf["mamba_expand"] * d, "H": conf["n_mamba_heads"],
        "P": conf["mamba_headdim"], "G": conf["mamba_ngroups"],
        "N": conf["mamba_d_state"], "K": conf["mamba_d_conv"],
        "da": conf["attention_hidden_size"], "Ha": conf["num_attention_heads"],
        "Hkv": conf["num_key_value_heads"], "hd": conf["attention_head_dim"],
        "f": conf["intermediate_size"], "r": conf["adapter_rank"],
        "V": conf["vocab_size"], "L": conf["num_hidden_layers"],
        "inv": sum(i < conf["num_hidden_layers"] for i in conf["hybrid_layer_ids"]),
        "nb": conf["num_mem_blocks"],
    }


def _plan(conf):
    """[(groups, layers a group, hybrid, first invocation)] per phase, as the
    program lays its weights out (see the module's docstring)."""
    n = conf["num_hidden_layers"]
    ids = [i for i in conf["hybrid_layer_ids"] if i < n]
    out = [(ids[0] if ids else n, 1, False, 0)] if not ids or ids[0] else []
    lengths = [b - a for a, b in zip(ids, ids[1:] + [n])]
    k = 0
    while k < len(lengths):
        m = 1
        while k + m < len(lengths) and lengths[k + m] == lengths[k]:
            m += 1
        out.append((m, lengths[k], True, k))
        k += m
    return out


# ---------------------------------------------------------------------------
# Counts, from shapes
# ---------------------------------------------------------------------------
def _mamba_matmul(m) -> int:
    conv_dim = m["inner"] + 2 * m["G"] * m["N"]
    return (m["d"] * (m["inner"] + conv_dim + m["H"]) + m["K"] * conv_dim
            + m["inner"] * m["d"])


def _invocation_matmul(m) -> int:
    """A shared block's weights, and the invocation's adapter and linear."""
    attn = m["da"] * (m["Ha"] + 2 * m["Hkv"]) * m["hd"] + m["Ha"] * m["hd"] * m["d"]
    mlp = 3 * m["d"] * m["f"]
    return attn + mlp + m["r"] * (m["d"] + 2 * m["f"]) + m["d"] * m["d"]


def matmul_params(conf) -> int:
    """Weights that multiply every token: the Mamba2 projections and conv,
    each shared block once per invocation with its adapter and linear, and
    the (tied) output head."""
    m = _dims(conf)
    return (m["L"] * _mamba_matmul(m) + m["inv"] * _invocation_matmul(m)
            + m["d"] * m["V"])


def param_count(conf) -> int:
    m = _dims(conf)
    d, conv_dim = m["d"], m["inner"] + 2 * m["G"] * m["N"]
    mamba = _mamba_matmul(m) + d + conv_dim + 3 * m["H"] + m["inner"]
    shared = (m["da"] * (m["Ha"] + 2 * m["Hkv"]) * m["hd"] + m["Ha"] * m["hd"] * d
              + 3 * d * m["f"] + m["da"] + d)
    invocation = m["r"] * (d + 2 * m["f"]) + d * d
    return (m["V"] * d + d + m["L"] * mamba + m["nb"] * shared
            + m["inv"] * invocation)


def flops_per_token(conf, position: int) -> float:
    """Forward FLOPs of the token at `position` (0-based): two per weight
    multiply-add; the scores and weighted sum of each invocation's attention
    over position+1 positions; and two multiply-adds per SSM state element
    in each Mamba2 layer (the update and the read)."""
    m = _dims(conf)
    attn = 4.0 * m["inv"] * m["Ha"] * m["hd"] * (position + 1)
    ssm = 4.0 * m["L"] * m["H"] * m["P"] * m["N"]
    return 2.0 * matmul_params(conf) + attn + ssm


def decode_min_bytes(conf, contexts, weight_bytes: int = 2,
                     cache_bytes: int = 2, state_bytes: int = 4) -> float:
    """The least HBM traffic of one decode step over sequences at the given
    context lengths (positions already in the cache): every weight read
    once, each shared block once per invocation (a block does not stay in
    on-chip memory from one invocation to the next), the embedding rows of
    the step's tokens; each sequence's SSM and conv state of every layer
    read and written; and each invocation's live K/V rows read and the new
    one written."""
    m = _dims(conf)
    conv_dim = m["inner"] + 2 * m["G"] * m["N"]
    weights = (matmul_params(conf) + len(contexts) * m["d"]) * weight_bytes
    state = m["L"] * (m["H"] * m["P"] * m["N"] + (m["K"] - 1) * conv_dim) * state_bytes
    row = m["inv"] * 2 * m["Hkv"] * m["hd"] * cache_bytes
    return float(weights + 2 * state * len(contexts)
                 + sum(c + 1 for c in contexts) * row)


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


def _attention(q, k, v, scale):
    """Causal softmax attention, queries in blocks. q (B,S,H,D), k/v
    (B,S,KV,D) with query head h reading kv head h // (H // KV)."""
    b, s, h, dh = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    blk = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        qpos = i * blk + jnp.arange(blk)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * scale
        ok = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // blk))       # (nb,B,blk,H,D)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


def _ssd(x, dt, A, Bm, Cm, blk):
    """y_t = sum_{s<=t} exp(sum_{s<i<=t} dt_i A) dt_s (C_t . B_s) x_s, from a
    zero state: within a block of `blk` positions the masked quadratic form,
    between blocks the state h (B,H,P,N) carried forward.  x (B,S,H,P),
    dt (B,S,H), A (H,), Bm/Cm (B,S,G,N)."""
    b, s, h, p = x.shape
    rep = h // Bm.shape[2]
    Bm, Cm = jnp.repeat(Bm, rep, axis=2), jnp.repeat(Cm, rep, axis=2)
    blk = min(blk, s)
    lower = jnp.tril(jnp.ones((blk, blk), bool))[None, :, :, None]

    def block(hst, i):
        xb, db, bb, cb = (jax.lax.dynamic_slice_in_dim(a, i * blk, blk, axis=1)
                          for a in (x, dt, Bm, Cm))
        cum = jnp.cumsum(db * A, axis=1)                          # (B,L,H)
        seg = jnp.where(lower, cum[:, :, None] - cum[:, None], -jnp.inf)
        w = jnp.einsum("bthn,bshn->btsh", cb, bb, precision=HIGHEST) \
            * jnp.exp(seg) * db[:, None]
        y = jnp.einsum("btsh,bshp->bthp", w, xb, precision=HIGHEST) \
            + jnp.exp(cum)[..., None] * jnp.einsum("bthn,bhpn->bthp", cb, hst,
                                                   precision=HIGHEST)
        last = db * jnp.exp(cum[:, -1:] - cum)                    # (B,L,H)
        hst = jnp.exp(cum[:, -1])[..., None, None] * hst + jnp.einsum(
            "bsh,bshp,bshn->bhpn", last, xb, bb, precision=HIGHEST)
        return hst, y

    h0 = jnp.zeros((b, h, p, Bm.shape[-1]), F32)
    _, ys = jax.lax.scan(block, h0, jnp.arange(s // blk))         # (nb,B,L,H,P)
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)


def _mamba2(mw, u, conf, fp8):
    m, eps = _dims(conf), conf["rms_norm_eps"]
    b, s, _ = u.shape
    inner, H, P, G, N, K = m["inner"], m["H"], m["P"], m["G"], m["N"], m["K"]
    z = _mm("bsd,di->bsi", u, mw["in_proj"], fp8)
    xbc = _mm("bsd,dc->bsc", u, mw["xbc_proj"], fp8)
    dt = jax.nn.softplus(_mm("bsd,dh->bsh", u, mw["dt_proj"], fp8)
                         + mw["dt_bias"].astype(F32))
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv_w = mw["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(pad[:, i:i + s] * conv_w[i] for i in range(K))
                      + mw["conv_b"].astype(F32))
    x = xbc[..., :inner].reshape(b, s, H, P)
    Bm = xbc[..., inner:inner + G * N].reshape(b, s, G, N)
    Cm = xbc[..., inner + G * N:].reshape(b, s, G, N)
    A = -jnp.exp(mw["A_log"].astype(F32))
    y = _ssd(x, dt, A, Bm, Cm, conf["chunk_size"])
    y = y + mw["D_skip"].astype(F32)[:, None] * x
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, G, inner // G)
    y = _norm(y, mw["ssm_norm"].reshape(G, -1), eps).reshape(b, s, inner)
    return _mm("bsi,id->bsd", y, mw["out_proj"], fp8)


def _shared(sw, iw, x, e, conf, pos, fp8):
    """One invocation of a shared block: its output t, which feeds the next
    Mamba2 layer's input and not the residual."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    scale = (conf["attention_head_dim"] / 2) ** -0.5
    s = _norm(jnp.concatenate([x, e], axis=-1), sw["norm1"], eps)
    a = sw["mixer"]
    q = _rope(_mm("bsd,dhk->bshk", s, a["wq"], fp8), pos, theta)
    k = _rope(_mm("bsd,dhk->bshk", s, a["wk"], fp8), pos, theta)
    v = _mm("bsd,dhk->bshk", s, a["wv"], fp8)
    o = _mm("bshk,hkd->bsd", _attention(q, k, v, scale), a["wo"], fp8)
    h = _norm(o, sw["norm2"], eps)
    f = sw["ffn"]
    lo = _mm("bsd,dr->bsr", h, iw["adapter_in"], fp8)
    g = _mm("bsd,df->bsf", h, f["w_gate"], fp8) + _mm("bsr,rf->bsf", lo, iw["adapter_gate"], fp8)
    u = _mm("bsd,df->bsf", h, f["w_up"], fp8) + _mm("bsr,rf->bsf", lo, iw["adapter_up"], fp8)
    mo = _mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * u, f["w_down"], fp8)
    return _mm("bsd,de->bse", mo, iw["linear"], fp8)


def forward(w, conf, tokens, *, fp8: bool = False):
    """tokens (B,S) int32, S a multiple of Q_BLOCK and of chunk_size or below
    them -> logits (B,S,V) float32.  With fp8, every matmul with a weight
    takes both its operands rounded to scaled float8: the control."""
    eps, nb = conf["rms_norm_eps"], conf["num_mem_blocks"]
    pos = jnp.arange(tokens.shape[1])
    x = e = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    for p, (_, layers, hybrid, first) in enumerate(_plan(conf)):
        def group(x, xs, layers=layers, hybrid=hybrid, first=first):
            gw, g = xs
            t = 0.0
            if hybrid:
                sw = jax.tree_util.tree_map(lambda a: a[(first + g) % nb], w["shared"])
                t = _shared(sw, gw["hybrid"], x, e, conf, pos, fp8)
            for j in range(layers):
                lw = gw[f"slot{j}"]
                u = _norm(x + t if j == 0 else x, lw["norm1"], eps)
                x = x + _mamba2(lw["mixer"], u, conf, fp8)
            return x, None

        stacked = w[f"phase{p}"]
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        x, _ = jax.lax.scan(group, x, (stacked, jnp.arange(n)))
    x = _norm(x, w["final_norm"], eps)
    return _mm("bsd,vd->bsv", x, w["embed"], fp8)
