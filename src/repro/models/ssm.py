"""SSM family blocks: Mamba2 (zamba2), mLSTM + sLSTM (xlstm).

All three expose (init, forward, decode_step, cache_shape):
  forward     -- full-sequence (train / prefill), chunkwise-parallel scans
  decode_step -- single-token recurrent update against carried state
State tensors are fp32 (recurrence stability); activations follow cfg.dtype.

TPU adaptation (DESIGN.md): the GPU selective-scan kernels become chunked
matmul scans (MXU work) -- Mamba2 via kernels/ssm_scan (Pallas) or the
chunked-jnp twin; mLSTM via an analogous stabilised chunked form below.
sLSTM is inherently sequential (scalar recurrence) and stays a lax.scan.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels import ops
from . import modules as nn
from .sharding import constrain

Params = Any


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================
def mamba2_init(key, cfg: ArchConfig, dtype) -> Params:
    """Mamba2 mixer params.  A and dt_bias follow Mamba2's published
    initialisation: A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1]
    stored through the inverse softplus."""
    d, inner, n, kconv = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    h = cfg.ssm_heads
    ks = nn.split_keys(key, 7)
    conv_dim = inner + 2 * cfg.ssm_groups * n     # x, B, C all pass the conv
    dt = jnp.exp(jax.random.uniform(ks[5], (h,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": nn.dense_init(ks[0], (d, inner), fan_in=d, dtype=dtype),      # gate z
        "xbc_proj": nn.dense_init(ks[1], (d, conv_dim), fan_in=d, dtype=dtype),
        "conv_w": nn.dense_init(ks[2], (kconv, conv_dim), fan_in=kconv, dtype=dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_proj": nn.dense_init(ks[3], (d, h), fan_in=d, dtype=dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[6], (h,), jnp.float32, 1.0, 16.0)),
        "D_skip": jnp.ones((h,), dtype),
        "ssm_norm": jnp.zeros((inner,), dtype),
        "out_proj": nn.dense_init(ks[4], (inner, d), fan_in=inner, dtype=dtype),
    }


def _mamba2_in(p: Params, x: jax.Array):
    """Projections of x (B,S,D), all in f32: gate z, the conv's input xBC,
    and dt = softplus(x W_dt + dt_bias) (no clamp)."""
    f32 = jnp.float32
    z = jnp.einsum("bsd,di->bsi", x, p["in_proj"], preferred_element_type=f32)
    xbc = jnp.einsum("bsd,dc->bsc", x, p["xbc_proj"], preferred_element_type=f32)
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", x, p["dt_proj"],
                                    preferred_element_type=f32)
                         + p["dt_bias"].astype(f32))
    return z, xbc, dt


def _mamba2_split(xbc: jax.Array, cfg: ArchConfig):
    """Post-conv xBC (...,C) -> x (...,H,P), B (...,G,N), C (...,G,N)."""
    inner, n, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    xin, Bm, Cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    lead = xbc.shape[:-1]
    return (xin.reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            Bm.reshape(lead + (g, n)), Cm.reshape(lead + (g, n)))


def _mamba2_out(p: Params, y: jax.Array, xin: jax.Array, z: jax.Array,
                cfg: ArchConfig):
    """y, xin (...,H,P) f32 -> + D x, RMSNorm over each group of heads of
    y * silu(z), then out_proj."""
    y = y + xin * p["D_skip"].astype(jnp.float32)[:, None]
    lead, g = y.shape[:-2], cfg.ssm_groups
    y = y.reshape(lead + (cfg.d_inner,)) * jax.nn.silu(z)
    y = nn.rms_norm(y.reshape(lead + (g, cfg.d_inner // g)),
                    p["ssm_norm"].reshape(g, -1), cfg.norm_eps)
    y = y.reshape(lead + (cfg.d_inner,)).astype(p["out_proj"].dtype)
    return jnp.einsum("...i,id->...d", y, p["out_proj"])


def _conv_silu(p: Params, full: jax.Array, s: int) -> jax.Array:
    """silu of the causal depthwise conv (with bias) of the last s of
    `full` (B,k-1+s,C), in f32: the sum of k products cancels, and bf16
    rounding of its terms would come out relatively large."""
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(full[:, i:i + s] * w[i] for i in range(w.shape[0]))
    return jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))


def _mamba2_seq(p: Params, x: jax.Array, cfg: ArchConfig, state=None):
    """S tokens at once from `state` (None: zero state, the start of a
    sequence): projections, causal conv and gating batched over the
    tokens, the state recurrence in the chunked SSD form.  Returns
    (out (B,S,D), final state)."""
    b, s, _ = x.shape
    k = cfg.ssm_conv
    z, xbc, dt = _mamba2_in(p, x)
    tail = (jnp.zeros((b, k - 1, xbc.shape[-1]), jnp.float32) if state is None
            else state["conv"])
    full = jnp.concatenate([tail, xbc], axis=1)                     # (B,k-1+S,C)
    xin, Bm, Cm = _mamba2_split(_conv_silu(p, full, s), cfg)
    xin = constrain(xin, "batch", None, "model", None)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    chunk_unroll = cfg.chunk_unroll if cfg.chunk_unroll is not None \
        else cfg.scan_unroll
    y, h = ops.ssm_scan(xin, dt, A, Bm, Cm, chunk=cfg.ssm_chunk,
                        h0=None if state is None else state["ssm"],
                        use_kernel=cfg.use_kernels, unroll=chunk_unroll)
    out = _mamba2_out(p, y, xin, z, cfg)
    return out, {"ssm": h, "conv": full[:, s:]}


def mamba2_forward(p: Params, x: jax.Array, cfg: ArchConfig,
                   *, return_state: bool = False):
    out, state = _mamba2_seq(p, x, cfg)
    return (out, state) if return_state else out


def mamba2_decode(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    """x: (B,1,D); cache {ssm:(B,H,P,N) f32, conv:(B,k-1,convdim) f32}.
    Head h reads B/C group h // (H // G).  B and C are repeated to the
    heads, so the state is updated in its own (B,H,P,N) shape: split into
    groups, the TPU would retile every layer's state for its update."""
    z, xbc_new, dt = _mamba2_in(p, x)
    window = jnp.concatenate([cache["conv"], xbc_new], axis=1)
    xin, Bm, Cm = _mamba2_split(_conv_silu(p, window, 1)[:, 0], cfg)  # (B,H,P),(B,G,N)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = dt[:, 0]                                                  # (B,H)
    rep = cfg.ssm_heads // cfg.ssm_groups                          # heads per group
    Bh, Ch = (jnp.repeat(m, rep, axis=1) for m in (Bm, Cm))        # (B,H,N)
    decay = jnp.exp(A[None] * dt)                                  # (B,H)
    inject = jnp.einsum("bh,bhp,bhn->bhpn", dt, xin, Bh)
    hstate = cache["ssm"] * decay[..., None, None] + inject
    y = jnp.einsum("bhpn,bhn->bhp", hstate, Ch)
    out = _mamba2_out(p, y, xin, z[:, 0], cfg)[:, None, :]
    return out, {"ssm": hstate, "conv": window[:, 1:, :]}


def mamba2_prefill(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    """Chunk prefill: C tokens against the carried state, the projections,
    conv and gating batched over the chunk and the recurrence in the
    chunked SSD form.  Agrees with C successive `mamba2_decode` calls to
    rounding (another order of the same sums), not bit for bit."""
    return _mamba2_seq(p, x, cfg, state=cache)


def mamba2_cache_shape(cfg: ArchConfig, batch: int):
    inner, n = cfg.d_inner, cfg.ssm_state
    return {"ssm": (batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
            "conv": (batch, cfg.ssm_conv - 1, inner + 2 * cfg.ssm_groups * n)}


# ===========================================================================
# mLSTM (xLSTM matrix memory) -- chunkwise-parallel stabilised form
# ===========================================================================
def mlstm_init(key, cfg: ArchConfig, dtype) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    ks = nn.split_keys(key, 7)
    return {
        "w_qx": nn.dense_init(ks[0], (d, h * hd), fan_in=d, dtype=dtype),
        "w_kx": nn.dense_init(ks[1], (d, h * hd), fan_in=d, dtype=dtype),
        "w_vx": nn.dense_init(ks[2], (d, h * hd), fan_in=d, dtype=dtype),
        "w_i": nn.dense_init(ks[3], (d, h), fan_in=d, dtype=dtype),
        "w_f": nn.dense_init(ks[4], (d, h), fan_in=d, dtype=dtype),
        "b_i": jnp.zeros((h,), dtype),
        "b_f": jnp.full((h,), 3.0, dtype),        # forget-gate bias ~ remember
        "w_o": nn.dense_init(ks[5], (d, h * hd), fan_in=d, dtype=dtype),
        "out_proj": nn.dense_init(ks[6], (h * hd, d), fan_in=d, dtype=dtype),
        "scale": jnp.zeros((d,), dtype),          # pre-out groupnorm-ish scale
    }


def _mlstm_chunked(q, k, v, logi, logf, chunk: int, state=None, unroll: bool = False):
    """Stabilised chunkwise mLSTM. q,k,v: (B,S,H,D); logi/logf: (B,S,H) fp32.

    Returns (y (B,S,H,D), state (C,n,m)).  Matches kernels.ref.mlstm_scan_ref
    (y is stabiliser-invariant)."""
    b, s, h, d = q.shape
    t = min(chunk, s)
    pad = (-s) % t
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))
    nc = q.shape[1] // t
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, nc, t, h, d)
    kf = k.astype(jnp.float32).reshape(b, nc, t, h, d)
    vf = v.astype(jnp.float32).reshape(b, nc, t, h, d)
    li = logi.reshape(b, nc, t, h)
    lf = logf.reshape(b, nc, t, h)
    tri = jnp.tril(jnp.ones((t, t), jnp.float32))

    if state is None:
        state = (jnp.zeros((b, h, d, d), jnp.float32),
                 jnp.zeros((b, h, d), jnp.float32),
                 jnp.full((b, h), -1e30, jnp.float32))

    def chunk_step(carry, args):
        C, nvec, m = carry
        qc, kc, vc, lic, lfc = args                    # (B,t,H,*)
        bcum = jnp.cumsum(lfc, axis=1)                 # (B,t,H) cumulative logf
        # intra-chunk log weights w[t,s] = bcum_t - bcum_s + li_s  (s<=t)
        wlog = bcum[:, :, None, :] - bcum[:, None, :, :] + lic[:, None, :, :]
        wlog = jnp.where(tri[None, :, :, None] > 0, wlog, -jnp.inf)
        glog = bcum + m[:, None, :]                    # state contribution decay
        m_row = jnp.maximum(jnp.max(wlog, axis=2), glog)           # (B,t,H)
        m_row = jnp.maximum(m_row, -1e30)
        wexp = jnp.exp(wlog - m_row[:, :, None, :])                # (B,t,s,H)
        gexp = jnp.exp(glog - m_row)                               # (B,t,H)
        scores = jnp.einsum("bthd,bshd->btsh", qc, kc) * wexp
        y_intra = jnp.einsum("btsh,bshd->bthd", scores, vc)
        y_state = gexp[..., None] * jnp.einsum("bhde,bthe->bthd", C, qc)
        nq = (jnp.einsum("btsh,bshd->bthd", wexp, kc) * qc).sum(-1) \
            + gexp * jnp.einsum("bthd,bhd->bth", qc, nvec)
        denom = jnp.maximum(jnp.abs(nq), jnp.exp(-m_row))
        y = (y_intra + y_state) / denom[..., None]
        # carry update (end of chunk), stabilised at m_new
        m_new = jnp.maximum(bcum[:, -1] + m, jnp.max(lic + (bcum[:, -1:] - bcum), axis=1))
        c_decay = jnp.exp(bcum[:, -1] + m - m_new)                 # (B,H)
        inj_w = jnp.exp(lic + (bcum[:, -1:] - bcum) - m_new[:, None])  # (B,t,H)
        C_new = C * c_decay[..., None, None] + jnp.einsum(
            "bthd,bthe,bth->bhde", vc, kc, inj_w)
        n_new = nvec * c_decay[..., None] + jnp.einsum("bthd,bth->bhd", kc, inj_w)
        return (C_new, n_new, m_new), y

    args = tuple(a.transpose(1, 0, *range(2, a.ndim)) for a in (qf, kf, vf, li, lf))
    state, ys = jax.lax.scan(chunk_step, state, args, unroll=True if unroll else 1)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, nc * t, h, d)[:, :s]
    return y, state


def mlstm_forward(p: Params, x: jax.Array, cfg: ArchConfig,
                  *, return_state: bool = False):
    b, s, d = x.shape
    h = cfg.n_heads
    hd = d // h
    q = jnp.einsum("bsd,de->bse", x, p["w_qx"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, p["w_kx"]).reshape(b, s, h, hd)
    v = jnp.einsum("bsd,de->bse", x, p["w_vx"]).reshape(b, s, h, hd)
    q = constrain(q, "batch", None, "model")
    logi = (jnp.einsum("bsd,dh->bsh", x, p["w_i"]) + p["b_i"]).astype(jnp.float32)
    logf = jax.nn.log_sigmoid(
        (jnp.einsum("bsd,dh->bsh", x, p["w_f"]) + p["b_f"]).astype(jnp.float32))
    if cfg.use_kernels and not return_state:
        from ..kernels.mlstm_scan import mlstm_scan as _mlstm_pallas
        y = _mlstm_pallas(q, k, v, logi, logf, chunk=cfg.ssm_chunk,
                          interpret=jax.default_backend() != "tpu")
        state = None
    else:
        chunk_unroll = cfg.chunk_unroll if cfg.chunk_unroll is not None \
            else cfg.scan_unroll
        y, state = _mlstm_chunked(q, k, v, logi, logf, cfg.ssm_chunk,
                                  unroll=chunk_unroll)
    o = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, p["w_o"]))
    y = y.reshape(b, s, h * hd).astype(x.dtype) * o
    out = jnp.einsum("bse,ed->bsd", nn.rms_norm(y, p["scale"], cfg.norm_eps),
                     p["out_proj"])
    if return_state:
        return out, {"C": state[0], "n": state[1], "m": state[2]}
    return out


def mlstm_decode(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    """One-step recurrent mLSTM. cache {C:(B,H,D,D), n:(B,H,D), m:(B,H)}."""
    b, _, d = x.shape
    h = cfg.n_heads
    hd = d // h
    q = jnp.einsum("bsd,de->bse", x, p["w_qx"])[:, 0].reshape(b, h, hd).astype(jnp.float32)
    k = jnp.einsum("bsd,de->bse", x, p["w_kx"])[:, 0].reshape(b, h, hd).astype(jnp.float32)
    v = jnp.einsum("bsd,de->bse", x, p["w_vx"])[:, 0].reshape(b, h, hd).astype(jnp.float32)
    logi = (jnp.einsum("bsd,dh->bsh", x, p["w_i"])[:, 0] + p["b_i"]).astype(jnp.float32)
    logf = jax.nn.log_sigmoid(
        (jnp.einsum("bsd,dh->bsh", x, p["w_f"])[:, 0] + p["b_f"]).astype(jnp.float32))
    C, nvec, m = cache["C"], cache["n"], cache["m"]
    m_new = jnp.maximum(logf + m, logi)
    fe = jnp.exp(logf + m - m_new)
    ie = jnp.exp(logi - m_new)
    C = C * fe[..., None, None] + ie[..., None, None] * jnp.einsum("bhd,bhe->bhde", v, k)
    nvec = nvec * fe[..., None] + ie[..., None] * k
    qs = q * hd ** -0.5
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", nvec, qs)), jnp.exp(-m_new))
    y = jnp.einsum("bhde,bhe->bhd", C, qs) / denom[..., None]
    o = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, p["w_o"])[:, 0])
    y = y.reshape(b, h * hd).astype(x.dtype) * o
    out = jnp.einsum("be,ed->bd", nn.rms_norm(y, p["scale"], cfg.norm_eps),
                     p["out_proj"])[:, None, :]
    return out, {"C": C, "n": nvec, "m": m_new}


def mlstm_prefill(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    """Chunk prefill: scan the exact one-step recurrence (decode twin)."""
    def step(carry, xt):
        out, new = mlstm_decode(p, xt[:, None, :], carry, cfg)
        return new, out[:, 0]

    carry, ys = jax.lax.scan(step, cache, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), carry


def mlstm_cache_shape(cfg: ArchConfig, batch: int):
    h = cfg.n_heads
    hd = cfg.d_model // h
    return {"C": (batch, h, hd, hd), "n": (batch, h, hd), "m": (batch, h)}


# ===========================================================================
# sLSTM (scalar memory, sequential)
# ===========================================================================
def slstm_init(key, cfg: ArchConfig, dtype) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    ks = nn.split_keys(key, 9)
    p = {"out_proj": nn.dense_init(ks[8], (h * hd, d), fan_in=d, dtype=dtype),
         "scale": jnp.zeros((d,), dtype)}
    for name, kk in zip(("w_i", "w_f", "w_z", "w_o"), ks[:4]):
        p[name] = nn.dense_init(kk, (d, h * hd), fan_in=d, dtype=dtype)
    for name, kk in zip(("r_i", "r_f", "r_z", "r_o"), ks[4:8]):
        # block-diagonal recurrent weights: per-head (hd, hd)
        p[name] = nn.dense_init(kk, (h, hd, hd), fan_in=hd, dtype=dtype)
    p["b_i"] = jnp.zeros((h * hd,), dtype)
    p["b_f"] = jnp.full((h * hd,), 3.0, dtype)
    return p


def _slstm_step(p, cfg, carry, xt):
    """xt: (B, D_in-projected gates preacts computed outside for speed)."""
    c, n, m, hprev = carry                                        # (B,H,hd) each
    b = hprev.shape[0]
    h_heads, hd = hprev.shape[1], hprev.shape[2]
    xi, xf, xz, xo = xt                                           # (B, H*hd) preacts

    def rec(w, hv):
        return jnp.einsum("bhe,hef->bhf", hv, w)

    i_pre = xi.reshape(b, h_heads, hd) + rec(p["r_i"], hprev)
    f_pre = xf.reshape(b, h_heads, hd) + rec(p["r_f"], hprev)
    z_pre = xz.reshape(b, h_heads, hd) + rec(p["r_z"], hprev)
    o_pre = xo.reshape(b, h_heads, hd) + rec(p["r_o"], hprev)
    logf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    logi = i_pre.astype(jnp.float32)
    m_new = jnp.maximum(logf + m, logi)
    fe, ie = jnp.exp(logf + m - m_new), jnp.exp(logi - m_new)
    c_new = fe * c + ie * jnp.tanh(z_pre.astype(jnp.float32))
    n_new = fe * n + ie
    h_new = jax.nn.sigmoid(o_pre.astype(jnp.float32)) * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, m_new, h_new.astype(hprev.dtype))


def slstm_forward(p: Params, x: jax.Array, cfg: ArchConfig,
                  *, return_state: bool = False):
    b, s, d = x.shape
    h, hd = cfg.n_heads, d // cfg.n_heads
    pre = {g: jnp.einsum("bsd,de->bse", x, p[f"w_{g}"]) + p[f"b_{g}"]
           if f"b_{g}" in p else jnp.einsum("bsd,de->bse", x, p[f"w_{g}"])
           for g in ("i", "f", "z", "o")}
    init = (jnp.zeros((b, h, hd), jnp.float32), jnp.zeros((b, h, hd), jnp.float32),
            jnp.full((b, h, hd), -1e30, jnp.float32), jnp.zeros((b, h, hd), jnp.float32))

    def step(carry, t):
        xt = tuple(pre[g][:, t] for g in ("i", "f", "z", "o"))
        new = _slstm_step(p, cfg, carry, xt)
        return new, new[3]

    carry, hs = jax.lax.scan(step, init, jnp.arange(s))
    y = hs.transpose(1, 0, 2, 3).reshape(b, s, h * hd).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", nn.rms_norm(y, p["scale"], cfg.norm_eps),
                     p["out_proj"])
    if return_state:
        return out, {"c": carry[0], "n": carry[1], "m": carry[2], "h": carry[3]}
    return out


def slstm_decode(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    carry = (cache["c"], cache["n"], cache["m"], cache["h"])
    xt = tuple((jnp.einsum("bsd,de->bse", x, p[f"w_{g}"])[:, 0]
                + (p[f"b_{g}"] if f"b_{g}" in p else 0)) for g in ("i", "f", "z", "o"))
    c, n, m, hnew = _slstm_step(p, cfg, carry, xt)
    b, d = x.shape[0], x.shape[2]
    y = hnew.reshape(b, -1).astype(x.dtype)
    out = jnp.einsum("be,ed->bd", nn.rms_norm(y, p["scale"], cfg.norm_eps),
                     p["out_proj"])[:, None, :]
    return out, {"c": c, "n": n, "m": m, "h": hnew}


def slstm_prefill(p: Params, x: jax.Array, cache: dict, cfg: ArchConfig):
    """Chunk prefill: scan the exact one-step recurrence (decode twin)."""
    def step(carry, xt):
        out, new = slstm_decode(p, xt[:, None, :], carry, cfg)
        return new, out[:, 0]

    carry, ys = jax.lax.scan(step, cache, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), carry


def slstm_cache_shape(cfg: ArchConfig, batch: int):
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    shp = (batch, h, hd)
    return {"c": shp, "n": shp, "m": shp, "h": shp}
