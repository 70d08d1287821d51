"""Full language models (+ whisper enc-dec, qwen2-vl vision merge).

Three pure entry points used by steps.py / launch:
  init_params(key, cfg)                     -> params pytree (eval_shape-able)
  forward(params, cfg, batch, collect_cache)-> (logits, aux, cache|None)
  decode_step(params, cfg, token, positions, cache) -> (logits, new_cache)
plus init_cache(cfg, batch) building zeroed decode caches.

`batch` keys: tokens (B,S) int32; optional vision_embeds (B,n_vis,D),
mrope_positions (B,3,S), frames (B,enc_len,D) for audio.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as attn
from . import blocks
from . import modules as nn
from .sharding import constrain

Params = Any


def sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """positions: (B,S) -> (B,S,D) classic transformer sinusoid."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ArchConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    plan = blocks.build_plan(cfg)
    keys = nn.split_keys(key, 6 + len(plan))
    p: dict = {
        "embed": nn.dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                               fan_in=cfg.d_model, dtype=dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = nn.dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                     fan_in=cfg.d_model, dtype=dtype)
    cross = cfg.family == "audio"
    for pi, phase in enumerate(plan):
        pk = nn.split_keys(keys[2 + pi], phase.n_groups)
        groups = []
        for g in range(phase.n_groups):
            gk = nn.split_keys(pk[g], len(phase.kinds) + 1)
            group = {
                f"slot{j}": blocks.slot_init(gk[j], cfg, kind, ffn, dtype, cross=cross)
                for j, (kind, ffn) in enumerate(zip(phase.kinds, phase.ffns))
            }
            if phase.hybrid:            # this group's shared-block invocation
                group["hybrid"] = blocks.invocation_init(gk[-1], cfg, dtype)
            groups.append(group)
        p[f"phase{pi}"] = nn.stack_layers(groups)
    if cfg.family == "hybrid":          # zamba2 weight-shared attn+MLP blocks
        sk = nn.split_keys(keys[-2], cfg.n_shared_blocks)
        p["shared"] = nn.stack_layers([blocks.shared_init(k, cfg, dtype) for k in sk])
    if cfg.family == "audio":           # whisper encoder stack
        ek = nn.split_keys(keys[-1], cfg.encoder_layers)
        p["encoder"] = nn.stack_layers([
            blocks.slot_init(ek[i], cfg, "global", "mlp", dtype)
            for i in range(cfg.encoder_layers)])
    return p


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------
def _embed(params, cfg: ArchConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    return x


def _head(params, cfg: ArchConfig, x):
    x = nn.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["embed"].astype(x.dtype))
    return jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(x.dtype))


def _encoder(params, cfg: ArchConfig, frames):
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    x = frames.astype(cfg.compute_dtype)
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None], frames.shape[:2])
    x = x + sinusoid(pos, cfg.d_model).astype(x.dtype)

    def body(carry, gp):
        h = carry
        mix = attn.gqa_forward(gp["mixer"], nn.rms_norm(h, gp["norm1"], cfg.norm_eps),
                               pos, cfg, causal=False)
        h = h + mix
        h = h + blocks.mlp_forward(gp["ffn"], nn.rms_norm(h, gp["norm2"], cfg.norm_eps), cfg)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"],
                        unroll=True if cfg.scan_unroll else 1)
    return x


def _group_ids(phase):
    """The scan's group index, which a hybrid phase needs to pick its
    shared block; None (nothing scanned) elsewhere."""
    return jnp.arange(phase.n_groups) if phase.hybrid else None


def _shared_params(params, cfg: ArchConfig, phase, g):
    """Params of the shared block that group g of a hybrid phase invokes:
    block (first_invocation + g) mod n_shared_blocks, in turn."""
    b = (phase.first_invocation + g) % cfg.n_shared_blocks
    return jax.tree_util.tree_map(lambda a: a[b], params["shared"])


def _cached_window(cfg: ArchConfig, kv: dict) -> int:
    """Window of a shared block's cache, one layer's (B,S,H,hd) or stacked
    (G,B,S,H,hd): windowed iff it was built as a ring (size at most the
    window, see _shared_window)."""
    return cfg.sliding_window if kv["k"].shape[-3] <= cfg.sliding_window else 0


def _positions_for(cfg: ArchConfig, batch) -> jax.Array:
    tokens = batch["tokens"]
    if cfg.use_mrope:
        return batch["mrope_positions"]
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def _inputs(params, cfg: ArchConfig, batch):
    x = _embed(params, cfg, batch["tokens"])
    if cfg.family == "vlm" and "vision_embeds" in batch:
        nv = batch["vision_embeds"].shape[1]
        x = x.at[:, :nv].set(batch["vision_embeds"].astype(x.dtype))
    if cfg.family == "audio":
        b, s = batch["tokens"].shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = x + sinusoid(pos, cfg.d_model).astype(x.dtype)
    return constrain(x, "batch", None, None)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params, cfg: ArchConfig, batch, *, collect_cache: bool = False,
            cache_len: int = 0):
    """Returns (logits (B,S,V), aux_loss, cache or None).

    When collect_cache, KV caches are emitted padded to `cache_len`
    (>= S) so decode can continue from the prefill."""
    params = nn.cast_tree(params, cfg.compute_dtype)   # mixed precision
    plan = blocks.build_plan(cfg)
    positions = _positions_for(cfg, batch)
    x = emb = _inputs(params, cfg, batch)
    enc_out = _encoder(params, cfg, batch["frames"]) if cfg.family == "audio" else None
    aux = jnp.zeros((), jnp.float32)
    caches: dict = {}
    w = _shared_window(cfg, cache_len)

    for pi, phase in enumerate(plan):
        stacked = params[f"phase{pi}"]

        def group_fn(carry, xs, phase=phase):
            h, a = carry
            gp, g = xs
            gcache = {}
            shared = None
            if phase.hybrid:
                def attend(mp, s):
                    out, (k, v) = attn.gqa_forward(mp, s, positions, cfg,
                                                   window=w, return_kv=True)
                    return out, {"k": k, "v": v}
                shared, c = blocks.shared_block(_shared_params(params, cfg, phase, g),
                                                gp["hybrid"], h, emb, cfg, attend)
                if collect_cache:
                    kind = "local" if w else "global"
                    gcache["shared"] = _pad_cache(c, kind, cfg, cache_len, window=w)
            for j, (kind, ffn) in enumerate(zip(phase.kinds, phase.ffns)):
                enc_kv = None
                if enc_out is not None:
                    enc_kv = attn.cross_kv(gp[f"slot{j}"]["cross"], enc_out)
                h, c, aj = blocks.slot_forward(
                    gp[f"slot{j}"], h, positions, cfg, kind, ffn,
                    collect_cache=collect_cache, enc_kv=enc_kv,
                    shared=shared if j == 0 else None)
                if collect_cache:
                    c = _pad_cache(c, kind, cfg, cache_len)
                    if enc_kv is not None:
                        c = dict(c, cross_k=enc_kv[0], cross_v=enc_kv[1])
                    gcache[f"slot{j}"] = c
                a = a + aj
            h = constrain(h, "batch", None, None)
            return (h, a), (gcache if collect_cache else None)

        body = jax.checkpoint(group_fn) if cfg.remat else group_fn
        (x, aux), pc = jax.lax.scan(body, (x, aux), (stacked, _group_ids(phase)),
                                    unroll=True if cfg.scan_unroll else 1)
        if collect_cache:
            caches[f"phase{pi}"] = pc

    logits = _head(params, cfg, x)
    if collect_cache and cfg.family == "audio":
        caches["enc_len"] = jnp.full((x.shape[0],), enc_out.shape[1], jnp.int32)
    return logits, aux, (caches if collect_cache else None)


def _shared_window(cfg: ArchConfig, cache_len: int) -> int:
    """Zamba2 long-context adaptation: window the tied attention block when
    the decode budget exceeds the training window (DESIGN.md)."""
    if cfg.family == "hybrid" and cache_len and cache_len > 65536:
        return cfg.sliding_window
    return 0


def _pad_cache(c: dict, kind: str, cfg: ArchConfig, cache_len: int, window: int = 0):
    """Pad prefill-emitted kv to the decode cache length (ring-aware), and
    GQA's k/v to the cache's row width (attention.kv_width)."""
    if kind not in ("global", "local", "mla") or not cache_len:
        return c
    if kind != "mla":
        c = {n: attn.to_kv_width(a, attn.kv_width(cfg)) for n, a in c.items()}
    if kind == "local" or window:
        w = window or cfg.sliding_window
        size = min(w, cache_len)
        out = {}
        for name in ("k", "v"):
            kv = c[name]
            s = kv.shape[1]
            if s >= size:
                # last `size` positions, placed at their ring slots
                tail = kv[:, -size:]
                pos = jnp.arange(s - size, s) % size
                out[name] = jnp.zeros((kv.shape[0], size) + kv.shape[2:],
                                      kv.dtype).at[:, pos].set(tail)
            else:
                out[name] = jnp.pad(kv, ((0, 0), (0, size - s)) + ((0, 0),) * (kv.ndim - 2))
        for name in c:
            if name not in ("k", "v"):
                out[name] = c[name]
        return out
    out = {}
    for name, kv in c.items():
        s = kv.shape[1]
        out[name] = kv if s >= cache_len else jnp.pad(
            kv, ((0, 0), (0, cache_len - s)) + ((0, 0),) * (kv.ndim - 2))
    return out


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_step(params, cfg: ArchConfig, tokens, positions, cache):
    """tokens: (B,1); positions: (B,) (or (B,3) M-RoPE) absolute position of
    the new token.  Returns (logits (B,V), new_cache).

    Each phase's stacked cache rides in its layer scan's carry; only the
    params and the group index are scanned.  Group g writes what its step
    changes straight into the stack -- an attention layer the new token's
    row (its ring slot if windowed, the c_kv/k_rope rows for MLA), a
    recurrent layer its whole state -- and attention reads layer g of the
    stack.  No layer is sliced out and stacked again, so where the caller
    donates the cache (serving/continuous.py) the step writes one row per
    attention layer in place."""
    params = nn.cast_tree(params, cfg.compute_dtype)   # mixed precision
    plan = blocks.build_plan(cfg)
    x = emb = _embed(params, cfg, tokens)
    if cfg.family == "audio":
        x = x + sinusoid(positions[:, None], cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", None, None)
    new_cache: dict = {}

    for pi, phase in enumerate(plan):
        def group_fn(carry, xs, phase=phase):
            h, pc = carry
            gp, g = xs
            pc = dict(pc)
            shared = None
            if phase.hybrid:
                w = _cached_window(cfg, pc["shared"])
                shared, pc["shared"] = blocks.shared_block(
                    _shared_params(params, cfg, phase, g), gp["hybrid"], h, emb, cfg,
                    lambda mp, s: attn.gqa_decode(mp, s, pc["shared"], g, positions,
                                                  cfg, window=w))
            for j, (kind, ffn) in enumerate(zip(phase.kinds, phase.ffns)):
                h, pc[f"slot{j}"] = blocks.slot_decode(
                    gp[f"slot{j}"], h, pc[f"slot{j}"], g, positions, cfg, kind, ffn,
                    shared=shared if j == 0 else None)
            return (h, pc), None

        (x, new_cache[f"phase{pi}"]), _ = jax.lax.scan(
            group_fn, (x, cache[f"phase{pi}"]),
            (params[f"phase{pi}"], jnp.arange(phase.n_groups)),
            unroll=True if cfg.scan_unroll else 1)

    if cfg.family == "audio":
        new_cache["enc_len"] = cache["enc_len"]
    logits = _head(params, cfg, x[:, 0])
    return logits, new_cache


def prefill_chunk(params, cfg: ArchConfig, tokens, positions, cache):
    """Chunked prefill: C prompt tokens at once against the decode cache.

    tokens: (B,C); positions: (B,C) (or (B,3,C) M-RoPE) absolute positions,
    contiguous ascending per row.  Returns (logits (B,C,V), new_cache).

    This is the decode twin of `forward`: the per-phase scan structure is
    decode_step's, but each slot consumes the whole chunk -- attention kinds
    write their C cache rows and attend with decode-exact masking
    (attention.gqa_prefill / mla_prefill), recurrent kinds scan the exact
    recurrence across the chunk (ssm.*_prefill).  A P-token prompt costs
    O(P/C) calls instead of P decode steps, and the oracle suite
    (tests/test_prefill_oracle.py) pins its outputs to the teacher-forced
    decode_step reference."""
    if cfg.family == "audio":
        raise NotImplementedError("chunked prefill: audio enc-dec unsupported")
    params = nn.cast_tree(params, cfg.compute_dtype)   # mixed precision
    plan = blocks.build_plan(cfg)
    x = emb = _embed(params, cfg, tokens)
    x = constrain(x, "batch", None, None)
    new_cache: dict = {}

    for pi, phase in enumerate(plan):
        stacked = params[f"phase{pi}"]
        pcache = cache[f"phase{pi}"]

        def group_fn(h, xs, phase=phase):
            gp, gc, g = xs
            out_c = {}
            shared = None
            if phase.hybrid:
                w = _cached_window(cfg, gc["shared"])
                shared, out_c["shared"] = blocks.shared_block(
                    _shared_params(params, cfg, phase, g), gp["hybrid"], h, emb, cfg,
                    lambda mp, s: attn.gqa_prefill(mp, s, gc["shared"], positions,
                                                   cfg, window=w))
            for j, (kind, ffn) in enumerate(zip(phase.kinds, phase.ffns)):
                h, nc = blocks.slot_prefill(gp[f"slot{j}"], h, gc[f"slot{j}"],
                                            positions, cfg, kind, ffn,
                                            shared=shared if j == 0 else None)
                out_c[f"slot{j}"] = nc
            return h, out_c

        x, pc = jax.lax.scan(group_fn, x, (stacked, pcache, _group_ids(phase)),
                             unroll=True if cfg.scan_unroll else 1)
        new_cache[f"phase{pi}"] = pc

    logits = _head(params, cfg, x)
    return logits, new_cache


def init_cache(cfg: ArchConfig, batch: int, length: int) -> Any:
    """Zeroed decode caches (structure mirrors forward(collect_cache)).

    Every leaf under a ``phase*`` key is (G, batch, ...): the phase's layer
    groups, then one row per sequence.  ContinuousBatcher relies on this to
    write one slot's row of every phase leaf; only ``enc_len`` lies outside
    the phases."""
    plan = blocks.build_plan(cfg)
    cdt = cfg.compute_dtype
    cache: dict = {}
    for pi, phase in enumerate(plan):
        pc = {}
        for j, kind in enumerate(phase.kinds):
            shp = blocks.slot_cache_shape(cfg, kind, batch, length)
            dt = blocks.cache_dtypes(kind, cdt)
            c = {k: jnp.zeros((phase.n_groups,) + v, dt) for k, v in shp.items()}
            if cfg.family == "audio":
                hkv, hd = cfg.n_kv_heads, cfg.head_dim
                c["cross_k"] = jnp.zeros((phase.n_groups, batch, cfg.encoder_len, hkv, hd), cdt)
                c["cross_v"] = jnp.zeros((phase.n_groups, batch, cfg.encoder_len, hkv, hd), cdt)
            pc[f"slot{j}"] = c
        if phase.hybrid:
            w = _shared_window(cfg, length)
            shp = blocks.slot_cache_shape(
                cfg, "local" if w else "global", batch, length)
            pc["shared"] = {k: jnp.zeros((phase.n_groups,) + v, cdt)
                            for k, v in shp.items()}
        cache[f"phase{pi}"] = pc
    if cfg.family == "audio":
        cache["enc_len"] = jnp.zeros((batch,), jnp.int32)
    return cache
