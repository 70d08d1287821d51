"""Block assembly: per-layer "slots" (mixer + ffn), grouped into scan phases.

Every architecture is a sequence of layers; each layer is
    x = x + mixer(norm1(x));  x = x + ffn(norm2(x))        (ffn optional)
with mixer in {global, local, mla, mamba2, mlstm, slstm} and ffn in
{mlp, moe, none}.  Layers are grouped by the repeating pattern (gemma3:
5 local + 1 global; xlstm: 7 mlstm + 1 slstm; ...) and each phase is a
jax.lax.scan over stacked group params -- compact HLO so the 512-device
dry-run compiles on CPU in reasonable time.

Zamba2 (family "hybrid"): before each mamba2 layer listed in
`hybrid_layer_ids`, one of `n_shared_blocks` weight-shared attention+MLP
blocks (in turn) reads [x ; token embedding] and its per-invocation linear
feeds that layer's input: x <- x + Mamba2(norm(x + t)).  The shared blocks'
params live outside the scan stacks (closure, indexed per group); each
group of a hybrid phase starts with an invocation, whose adapter, linear
and KV cache are stacked per group.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as attn
from . import modules as nn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .sharding import constrain

Params = Any

MIXER_KINDS = ("global", "local", "mla", "mamba2", "mlstm", "slstm")
FFN_KINDS = ("mlp", "moe", "none")


@dataclasses.dataclass(frozen=True)
class Phase:
    kinds: tuple          # mixer kind per slot in the group
    ffns: tuple           # ffn kind per slot
    n_groups: int
    # zamba2: each group starts with shared-block invocation
    # first_invocation + g, which feeds slot0's input
    hybrid: bool = False
    first_invocation: int = 0


def _hybrid_plan(cfg: ArchConfig) -> list[Phase]:
    """Mamba2 layers with shared-block invocations before hybrid_layer_ids:
    the layers before the first invocation are one phase of one-layer
    groups; then each invocation opens a group that runs to the next one,
    and consecutive groups of one length share a phase.  A config cut to
    fewer layers keeps the invocations that fall inside them."""
    L = cfg.n_layers
    assert list(cfg.hybrid_layer_ids) == sorted(set(cfg.hybrid_layer_ids)) \
        and min(cfg.hybrid_layer_ids, default=0) >= 0, cfg.hybrid_layer_ids
    ids = tuple(i for i in cfg.hybrid_layer_ids if i < L)
    lead = ids[0] if ids else L
    phases = [Phase(("mamba2",), ("none",), lead)] if lead else []
    lengths = [b - a for a, b in zip(ids, ids[1:] + (L,))]
    k = 0
    while k < len(lengths):
        n = 1
        while k + n < len(lengths) and lengths[k + n] == lengths[k]:
            n += 1
        phases.append(Phase(("mamba2",) * lengths[k], ("none",) * lengths[k], n,
                            hybrid=True, first_invocation=k))
        k += n
    return phases


def build_plan(cfg: ArchConfig) -> list[Phase]:
    """Derive the scan-phase plan from the config."""
    L = cfg.n_layers
    if cfg.family == "ssm":                          # xlstm
        per = cfg.slstm_every or L
        kinds = tuple("mlstm" if (i + 1) % per else "slstm" for i in range(per))
        assert L % per == 0, "xlstm layer count must tile the sLSTM period"
        return [Phase(kinds, ("none",) * per, L // per)]
    if cfg.family == "hybrid":                       # zamba2
        return _hybrid_plan(cfg)
    ffn = "moe" if cfg.n_experts else "mlp"
    pattern = cfg.block_pattern
    phases = []
    if cfg.n_experts and cfg.first_layer_dense:      # deepseek: dense layer 0
        phases.append(Phase((pattern[0],), ("mlp",), 1))
        L -= 1
    per = len(pattern)
    full, rem = divmod(L, per)
    if full:
        phases.append(Phase(tuple(pattern), (ffn,) * per, full))
    if rem:
        phases.append(Phase(tuple(pattern[:rem]), (ffn,) * rem, 1))
    return phases


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
GATED_ACTS = {"swiglu": jax.nn.silu,
              "geglu": lambda g: jax.nn.gelu(g, approximate=False)}


def mlp_init(key, cfg: ArchConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = nn.split_keys(key, 3)
    if cfg.mlp_act in GATED_ACTS:
        return {"w_gate": nn.dense_init(ks[0], (d, f), dtype=dtype),
                "w_up": nn.dense_init(ks[1], (d, f), dtype=dtype),
                "w_down": nn.dense_init(ks[2], (f, d), fan_in=f, dtype=dtype)}
    return {"w_up": nn.dense_init(ks[0], (d, f), dtype=dtype),
            "w_down": nn.dense_init(ks[1], (f, d), fan_in=f, dtype=dtype)}


def mlp_forward(p: Params, x: jax.Array, cfg: ArchConfig,
                adapter: Optional[Params] = None) -> jax.Array:
    """act(gate) * up, or act(up) ungated, then down.  `adapter` (a zamba2
    invocation's params) adds its rank-r LoRA to gate and up."""
    lora = adapter is not None and cfg.adapter_rank > 0
    h = jnp.einsum("...d,df->...f", x, p["w_up"])
    if lora:
        a = jnp.einsum("...d,dr->...r", x, adapter["adapter_in"])
        h = h + jnp.einsum("...r,rf->...f", a, adapter["adapter_up"])
    if cfg.mlp_act in GATED_ACTS:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        if lora:
            g = g + jnp.einsum("...r,rf->...f", a, adapter["adapter_gate"])
        h = GATED_ACTS[cfg.mlp_act](g) * h
    else:
        h = nn.ACTIVATIONS[cfg.mlp_act](h)
    h = constrain(h, "batch", None, "model")
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# ---------------------------------------------------------------------------
# Zamba2 shared blocks
# ---------------------------------------------------------------------------
def shared_init(key, cfg: ArchConfig, dtype) -> Params:
    """One weight-shared attention+MLP block: attention from the
    2*d_model-wide [x ; embedding] back to d_model, then the MLP."""
    ks = nn.split_keys(key, 2)
    return {"norm1": jnp.zeros((cfg.shared_in,), dtype),
            "mixer": attn.gqa_init(ks[0], cfg, dtype, d_in=cfg.shared_in),
            "norm2": jnp.zeros((cfg.d_model,), dtype),
            "ffn": mlp_init(ks[1], cfg, dtype)}


def invocation_init(key, cfg: ArchConfig, dtype) -> Params:
    """What one invocation of a shared block owns: the linear into the next
    mamba layer's input, and the MLP adapter when adapter_rank > 0."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    ks = nn.split_keys(key, 4)
    p = {"linear": nn.dense_init(ks[0], (d, d), dtype=dtype)}
    if r:
        p["adapter_in"] = nn.dense_init(ks[1], (d, r), dtype=dtype)
        p["adapter_gate"] = nn.dense_init(ks[2], (r, f), dtype=dtype)
        p["adapter_up"] = nn.dense_init(ks[3], (r, f), dtype=dtype)
    return p


def shared_block(sp: Params, ip: Params, x: jax.Array, emb: jax.Array,
                 cfg: ArchConfig, attend):
    """One invocation: t = linear(MLP_k(norm2(attn(norm1([x ; emb]))))),
    with no residual inside.  `attend(mixer params, normed input)` returns
    (attention output, its cache).  Returns (t, cache)."""
    with jax.named_scope("shared_block"):
        s = nn.rms_norm(jnp.concatenate([x, emb], axis=-1), sp["norm1"],
                        cfg.norm_eps)
        with jax.named_scope("attention"):
            a, cache = attend(sp["mixer"], s)
        h = nn.rms_norm(a, sp["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            m = mlp_forward(sp["ffn"], h, cfg, adapter=ip)
        return jnp.einsum("...d,de->...e", m, ip["linear"]), cache


# ---------------------------------------------------------------------------
# Slots
# ---------------------------------------------------------------------------
_MIXER_INIT = {
    "global": attn.gqa_init, "local": attn.gqa_init, "mla": attn.mla_init,
    "mamba2": ssm_mod.mamba2_init, "mlstm": ssm_mod.mlstm_init,
    "slstm": ssm_mod.slstm_init,
}


def slot_init(key, cfg: ArchConfig, kind: str, ffn: str, dtype,
              cross: bool = False) -> Params:
    ks = nn.split_keys(key, 4)
    p = {"norm1": jnp.zeros((cfg.d_model,), dtype),
         "mixer": _MIXER_INIT[kind](ks[0], cfg, dtype)}
    if ffn != "none":
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        p["ffn"] = (moe_mod.moe_init(ks[1], cfg, dtype) if ffn == "moe"
                    else mlp_init(ks[1], cfg, dtype))
    if cross:   # whisper decoder: cross-attention sub-layer
        p["norm_x"] = jnp.zeros((cfg.d_model,), dtype)
        p["cross"] = attn.cross_init(ks[2], cfg, dtype)
    return p


def _mixer_forward(p, x, positions, cfg, kind, collect_cache: bool):
    window = cfg.sliding_window if kind == "local" else 0
    if kind in ("global", "local"):
        if collect_cache:
            out, (k, v) = attn.gqa_forward(p, x, positions, cfg, window=window,
                                           return_kv=True)
            return out, {"k": k, "v": v}
        return attn.gqa_forward(p, x, positions, cfg, window=window), None
    if kind == "mla":
        if collect_cache:
            out, c = attn.mla_forward(p, x, positions, cfg, return_cache=True)
            return out, c
        return attn.mla_forward(p, x, positions, cfg), None
    fwd = {"mamba2": ssm_mod.mamba2_forward, "mlstm": ssm_mod.mlstm_forward,
           "slstm": ssm_mod.slstm_forward}[kind]
    if collect_cache:
        return fwd(p, x, cfg, return_state=True)
    return fwd(p, x, cfg), None


def _mixer_decode(p, x, stack, g, positions, cfg, kind):
    """The mixer's one-token step against layer g of its slot's stacked
    cache.  Returns (out, the leaves it wrote, as whole stacks): an
    attention kind writes the new token's row, a recurrent kind rewrites
    its layer's state."""
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        return attn.gqa_decode(p, x, stack, g, positions, cfg, window=window)
    if kind == "mla":
        return attn.mla_decode(p, x, stack, g, positions, cfg)
    dec = {"mamba2": ssm_mod.mamba2_decode, "mlstm": ssm_mod.mlstm_decode,
           "slstm": ssm_mod.slstm_decode}[kind]
    state = {k: a[g] for k, a in stack.items()}
    out, new = dec(p, x, state, cfg)
    return out, {k: stack[k].at[g].set(v.astype(stack[k].dtype))
                 for k, v in new.items()}


def _norm_in(p: Params, x: jax.Array, cfg: ArchConfig, shared):
    """norm1 of the mixer's input: x, or x + t after a zamba2 shared block
    (t feeds the mixer only, not the residual)."""
    return nn.rms_norm(x if shared is None else x + shared, p["norm1"], cfg.norm_eps)


def slot_forward(p: Params, x: jax.Array, positions, cfg: ArchConfig,
                 kind: str, ffn: str, *, collect_cache: bool = False,
                 enc_kv=None, shared=None):
    mix_out, cache = _mixer_forward(p["mixer"], _norm_in(p, x, cfg, shared),
                                    positions, cfg, kind, collect_cache)
    x = x + mix_out
    if enc_kv is not None:
        x = x + attn.cross_forward(p["cross"], nn.rms_norm(x, p["norm_x"], cfg.norm_eps),
                                   enc_kv, cfg)
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h = nn.rms_norm(x, p["norm2"], cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe_mod.moe_forward(p["ffn"], h, cfg)
        else:
            y = mlp_forward(p["ffn"], h, cfg)
        x = x + y
    return x, cache, aux


def mixer_scope(kind: str) -> str:
    """Name scope of a mixer's ops: "attention" for the attention kinds,
    else the recurrent kind's own name.  A trace's op metadata carries it,
    so device time can be read by layer part across refactors."""
    return "attention" if kind in ("global", "local", "mla") else kind


def slot_decode(p: Params, x: jax.Array, stack, g, positions, cfg: ArchConfig,
                kind: str, ffn: str, *, shared=None):
    """One token through layer g of a phase: `stack` is the slot's cache
    stacked over the phase's groups.  Returns (x, the stack with what this
    step changed written at layer g); audio cross K/V is only read."""
    xn = _norm_in(p, x, cfg, shared)
    with jax.named_scope(mixer_scope(kind)):
        mix_out, written = _mixer_decode(p["mixer"], xn, stack, g, positions, cfg, kind)
    x = x + mix_out
    if "cross_k" in stack:
        x = x + attn.cross_decode(p["cross"], nn.rms_norm(x, p["norm_x"], cfg.norm_eps),
                                  (stack["cross_k"][g], stack["cross_v"][g]), cfg)
    if ffn != "none":
        h = nn.rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y = (moe_mod.moe_forward(p["ffn"], h, cfg)[0] if ffn == "moe"
                 else mlp_forward(p["ffn"], h, cfg))
        x = x + y
    return x, dict(stack, **written)


def _mixer_prefill(p, x, cache, positions, cfg, kind):
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        return attn.gqa_prefill(p, x, cache, positions, cfg, window=window)
    if kind == "mla":
        return attn.mla_prefill(p, x, cache, positions, cfg)
    pre = {"mamba2": ssm_mod.mamba2_prefill, "mlstm": ssm_mod.mlstm_prefill,
           "slstm": ssm_mod.slstm_prefill}[kind]
    return pre(p, x, cache, cfg)


def slot_prefill(p: Params, x: jax.Array, cache, positions, cfg: ArchConfig,
                 kind: str, ffn: str, *, shared=None):
    """Chunked-prefill twin of slot_decode: C tokens, decode-cache layout.

    Attention kinds batch all C queries against the cache with decode-exact
    masking; recurrent kinds carry their state across the chunk.  FFN / norms
    are position-independent row ops and run batched."""
    xn = _norm_in(p, x, cfg, shared)
    with jax.named_scope(mixer_scope(kind)):
        mix_out, new_cache = _mixer_prefill(p["mixer"], xn, cache, positions, cfg, kind)
    x = x + mix_out
    if ffn != "none":
        h = nn.rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y = (moe_mod.moe_forward(p["ffn"], h, cfg, no_drop=True)[0]
                 if ffn == "moe" else mlp_forward(p["ffn"], h, cfg))
        x = x + y
    return x, new_cache


def slot_cache_shape(cfg: ArchConfig, kind: str, batch: int, length: int):
    if kind == "global":
        return attn.gqa_cache_shape(cfg, batch, length)
    if kind == "local":
        return attn.gqa_cache_shape(cfg, batch, length, window=cfg.sliding_window)
    if kind == "mla":
        return attn.mla_cache_shape(cfg, batch, length)
    if kind == "mamba2":
        return ssm_mod.mamba2_cache_shape(cfg, batch)
    if kind == "mlstm":
        return ssm_mod.mlstm_cache_shape(cfg, batch)
    if kind == "slstm":
        return ssm_mod.slstm_cache_shape(cfg, batch)
    raise ValueError(kind)


def cache_dtypes(kind: str, compute_dtype):
    """SSM-ish states carry fp32; KV caches follow the compute dtype."""
    if kind in ("global", "local", "mla"):
        return compute_dtype
    return jnp.float32
