"""Block assembly: per-layer "slots" (mixer + ffn), grouped into scan phases.

Every architecture is a sequence of layers; each layer is
    x = x + mixer(norm1(x));  x = x + ffn(norm2(x))        (ffn optional)
with mixer in {global, local, mla, mamba2, mlstm, slstm} and ffn in
{mlp, moe, none}.  Layers are grouped by the repeating pattern (gemma3:
5 local + 1 global; xlstm: 7 mlstm + 1 slstm; ...) and each phase is a
jax.lax.scan over stacked group params -- compact HLO so the 512-device
dry-run compiles on CPU in reasonable time.

Zamba2's weight-TIED shared attention block is applied after each group of
`shared_attn_every` mamba layers; its params live outside the scan stack
(closure), while its per-invocation KV caches are stacked per group.
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
    shared_attn: bool = False   # zamba2: tied attention block after each group


def build_plan(cfg: ArchConfig) -> list[Phase]:
    """Derive the scan-phase plan from the config."""
    L = cfg.n_layers
    if cfg.family == "ssm":                          # xlstm
        per = cfg.slstm_every or L
        kinds = tuple("mlstm" if (i + 1) % per else "slstm" for i in range(per))
        assert L % per == 0, "xlstm layer count must tile the sLSTM period"
        return [Phase(kinds, ("none",) * per, L // per)]
    if cfg.family == "hybrid":                       # zamba2
        per = cfg.shared_attn_every
        full, rem = divmod(L, per)
        phases = [Phase(("mamba2",) * per, ("none",) * per, full, shared_attn=True)]
        if rem:
            phases.append(Phase(("mamba2",) * rem, ("none",) * rem, 1))
        return phases
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
def mlp_init(key, cfg: ArchConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = nn.split_keys(key, 3)
    if cfg.mlp_act == "swiglu":
        return {"w_gate": nn.dense_init(ks[0], (d, f), dtype=dtype),
                "w_up": nn.dense_init(ks[1], (d, f), dtype=dtype),
                "w_down": nn.dense_init(ks[2], (f, d), fan_in=f, dtype=dtype)}
    return {"w_up": nn.dense_init(ks[0], (d, f), dtype=dtype),
            "w_down": nn.dense_init(ks[1], (f, d), fan_in=f, dtype=dtype)}


def mlp_forward(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, p["w_up"])
    if cfg.mlp_act == "swiglu":
        h = nn.swiglu(h, jnp.einsum("...d,df->...f", x, p["w_gate"]))
    else:
        h = nn.ACTIVATIONS[cfg.mlp_act](h)
    h = constrain(h, "batch", None, "model")
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


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


def _mixer_decode(p, x, cache, positions, cfg, kind):
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        return attn.gqa_decode(p, x, cache, positions, cfg, window=window)
    if kind == "mla":
        return attn.mla_decode(p, x, cache, positions, cfg)
    dec = {"mamba2": ssm_mod.mamba2_decode, "mlstm": ssm_mod.mlstm_decode,
           "slstm": ssm_mod.slstm_decode}[kind]
    return dec(p, x, cache, cfg)


def slot_forward(p: Params, x: jax.Array, positions, cfg: ArchConfig,
                 kind: str, ffn: str, *, collect_cache: bool = False,
                 enc_kv=None):
    mix_out, cache = _mixer_forward(p["mixer"], nn.rms_norm(x, p["norm1"], cfg.norm_eps),
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


def slot_decode(p: Params, x: jax.Array, cache, positions, cfg: ArchConfig,
                kind: str, ffn: str, *, enc_kv=None):
    xn = nn.rms_norm(x, p["norm1"], cfg.norm_eps)
    with jax.named_scope(mixer_scope(kind)):
        mix_out, new_cache = _mixer_decode(p["mixer"], xn, cache, positions, cfg, kind)
    x = x + mix_out
    if enc_kv is not None:
        x = x + attn.cross_decode(p["cross"], nn.rms_norm(x, p["norm_x"], cfg.norm_eps),
                                  enc_kv, cfg)
    if ffn != "none":
        h = nn.rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y = (moe_mod.moe_forward(p["ffn"], h, cfg)[0] if ffn == "moe"
                 else mlp_forward(p["ffn"], h, cfg))
        x = x + y
    return x, new_cache


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
                 kind: str, ffn: str):
    """Chunked-prefill twin of slot_decode: C tokens, decode-cache layout.

    Attention kinds batch all C queries against the cache with decode-exact
    masking; recurrent kinds scan the exact decode recurrence.  FFN / norms
    are position-independent row ops and run batched."""
    xn = nn.rms_norm(x, p["norm1"], cfg.norm_eps)
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


def slot_decode_stacked(p: Params, x: jax.Array, stacked, g: int, positions,
                        cfg: ArchConfig, kind: str, ffn: str, *, enc_kv=None):
    """slot_decode against the layer-STACKED cache: attention kinds update
    in place via dynamic-update-slice (§Perf C3); recurrent kinds read the
    layer slice and write the (small) state back at group index g."""
    xn = nn.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        mix_out, stacked = attn.gqa_decode_stacked(p["mixer"], xn, stacked, g,
                                                   positions, cfg, window=window)
    elif kind == "mla":
        mix_out, stacked = attn.mla_decode_stacked(p["mixer"], xn, stacked, g,
                                                   positions, cfg)
    else:
        dec = {"mamba2": ssm_mod.mamba2_decode, "mlstm": ssm_mod.mlstm_decode,
               "slstm": ssm_mod.slstm_decode}[kind]
        state_keys = slot_cache_shape(cfg, kind, 1, 1).keys()
        layer_state = {k: stacked[k][g] for k in state_keys}
        mix_out, new_state = dec(p["mixer"], xn, layer_state, cfg)
        stacked = dict(stacked, **{k: stacked[k].at[g].set(
            new_state[k].astype(stacked[k].dtype)) for k in state_keys})
    x = x + mix_out
    if enc_kv is not None:
        x = x + attn.cross_decode(p["cross"], nn.rms_norm(x, p["norm_x"], cfg.norm_eps),
                                  enc_kv, cfg)
    if ffn != "none":
        h = nn.rms_norm(x, p["norm2"], cfg.norm_eps)
        y = (moe_mod.moe_forward(p["ffn"], h, cfg)[0] if ffn == "moe"
             else mlp_forward(p["ffn"], h, cfg))
        x = x + y
    return x, stacked


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
