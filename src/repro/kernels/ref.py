"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernels are validated against (tests/ sweeps
shapes & dtypes with assert_allclose), and also the default compute path on
CPU (interpret-mode Pallas is slow; model code dispatches via ops.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rmsnorm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def _gqa_expand(k: jax.Array, n_q_heads: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating kv heads.  Only the
    full-sequence oracle uses it; decode contracts grouped queries instead."""
    b, s, hkv, d = k.shape
    group = n_q_heads // hkv
    return jnp.repeat(k, group, axis=2) if group > 1 else k


def flash_attention_ref(
    q: jax.Array,              # (B, Sq, Hq, D)
    k: jax.Array,              # (B, Skv, Hkv, D)
    v: jax.Array,              # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,           # 0 = full; >0 = sliding window (causal)
    scale: float | None = None,
    q_offset: int = 0,         # absolute position of q[0] (for cached prefill)
) -> jax.Array:
    """Masked multi-head attention oracle, fp32 softmax accumulation.

    Dots use preferred_element_type=f32 on native-dtype operands rather
    than .astype(f32) inputs: casting k/v materialises f32 copies of the
    biggest tensors in the program (EXPERIMENTS.md §Perf C1)."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,              # (B, Hq, D) single query token per sequence
    k_cache: jax.Array,        # (B, S, Hkv, D)
    v_cache: jax.Array,        # (B, S, Hkv, D)
    cache_len: jax.Array,      # (B,) int32 valid lengths
    *,
    scale: float | None = None,
) -> jax.Array:
    """Single-token decode attention against a (padded) KV cache.

    Query head h reads KV head h // G (G = Hq // Hkv), as a repeat of the
    cache would give it, but the G query heads of a group are contracted
    together against the unexpanded cache: K and V are read once each, in
    their own dtype, and no per-query-head copy of the cache is built (a
    one-query matvec over a repeated cache lowers to an f32 broadcast of
    the whole cache).  With G = 1 the reshape is the identity."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qg = q.astype(k_cache.dtype).reshape(b, hkv, hq // hkv, d)   # (B,Hkv,G,D)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s)[None, :] < cache_len[:, None]          # (B, S)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d).astype(q.dtype)


def ssm_scan_ref(
    x: jax.Array,      # (B, S, H, P)   inputs per head
    dt: jax.Array,     # (B, S, H)      softplus'd timestep (>0)
    A: jax.Array,      # (H,)           negative decay rates
    Bm: jax.Array,     # (B, S, N)      input  projection (G=1 group)
    Cm: jax.Array,     # (B, S, N)      output projection
    *,
    h0: jax.Array | None = None,   # (B, H, P, N) initial state
):
    """Sequential Mamba2/SSD oracle.

    h_t = exp(A*dt_t) h_{t-1} + dt_t * (x_t outer B_t);  y_t = h_t . C_t
    Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf, Af = Bm.astype(jnp.float32), Cm.astype(jnp.float32), A.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def step(hprev, t):
        decay = jnp.exp(Af[None, :] * dtf[:, t])                     # (B,H)
        inject = jnp.einsum("bh,bhp,bn->bhpn", dtf[:, t], xf[:, t], Bf[:, t])
        hnew = hprev * decay[..., None, None] + inject
        y = jnp.einsum("bhpn,bn->bhp", hnew, Cf[:, t])
        return hnew, y

    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(s))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), h_final


def mlstm_scan_ref(
    q: jax.Array,      # (B, S, H, D)
    k: jax.Array,      # (B, S, H, D)
    v: jax.Array,      # (B, S, H, D)
    i_gate: jax.Array, # (B, S, H)  log-space input gate preact
    f_gate: jax.Array, # (B, S, H)  forget gate preact (sigmoid-log space)
):
    """Sequential mLSTM oracle (xLSTM matrix memory, stabilised).

    C_t = f_t C_{t-1} + i_t v_t k_t^T ; n_t = f_t n_{t-1} + i_t k_t
    y_t = C~_t q_t / max(|n~_t . q_t|, exp(-m_t))
    where C~, n~ are the exp(-m_t)-stabilised accumulators and
    m_t = max(log f_t + m_{t-1}, log i_t) -- the xLSTM stabilised form;
    y is invariant to the stabiliser, so chunked implementations with a
    different m agree exactly.  Returns y (B,S,H,D).
    """
    b, s, h, d = q.shape
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))            # (B,S,H)
    logi = i_gate.astype(jnp.float32)

    def step(carry, t):
        C, n, m = carry                                              # (B,H,D,D),(B,H,D),(B,H)
        m_new = jnp.maximum(logf[:, t] + m, logi[:, t])
        fe = jnp.exp(logf[:, t] + m - m_new)
        ie = jnp.exp(logi[:, t] - m_new)
        C = C * fe[..., None, None] + ie[..., None, None] * jnp.einsum(
            "bhd,bhe->bhde", vf[:, t], kf[:, t])
        n = n * fe[..., None] + ie[..., None] * kf[:, t]
        qt = qf[:, t] * (d ** -0.5)
        denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, qt)), jnp.exp(-m_new))
        y = jnp.einsum("bhde,bhe->bhd", C, qt) / denom[..., None]
        return (C, n, m_new), y

    init = (
        jnp.zeros((b, h, d, d), jnp.float32),
        jnp.zeros((b, h, d), jnp.float32),
        jnp.full((b, h), -1e30, jnp.float32),
    )
    _, ys = jax.lax.scan(step, init, jnp.arange(s))
    return ys.transpose(1, 0, 2, 3).astype(q.dtype)
