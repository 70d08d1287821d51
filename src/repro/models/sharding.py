"""Sharding substrate: logical-axis rules mapping params/activations onto the mesh.

The production mesh has axes ("data", "model") single-pod or
("pod", "data", "model") multi-pod (launch/mesh.py).  Model code never
touches jax.sharding directly -- it calls :func:`constrain` with *logical*
axis names; this module resolves them against the currently-active mesh.

Param sharding is rule-based: every parameter leaf has a descriptive key
(``wq``, ``w_down``, ``experts_up`` ...) and SHARDING_RULES maps that key to
a PartitionSpec *tail* applied to the trailing dims (leading stacked-layer
dims are None-padded).  GSPMD pads non-divisible dims, so e.g. 24 heads over
model=16 still lowers -- the waste shows up in the roofline flops ratio and
is hillclimbed in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# Logical axis -> mesh axis (or tuple of mesh axes).  "batch" spans the pod
# axis too when present so global_batch shards over every data-parallel chip.
LOGICAL_AXES = {
    "batch": ("pod", "data"),
    "model": ("model",),
    "expert": ("model",),   # expert-parallel rides the model axis
    None: None,
}

# "dp" profile (perf variant): the model axis carries batch instead --
# params replicated, no per-layer activation collectives.
DP_AXES = {
    "batch": ("pod", "data", "model"),
    "model": None,
    "expert": None,
    None: None,
}


def current_profile() -> str:
    return getattr(_state, "profile", "tp")


@contextlib.contextmanager
def use_profile(profile: str):
    prev = current_profile()
    _state.profile = profile
    try:
        yield
    finally:
        _state.profile = prev


def _axis_table():
    return DP_AXES if current_profile() == "dp" else LOGICAL_AXES


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate *mesh* for constrain()/param_shardings(). None deactivates."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """A device-free ``jax.sharding.AbstractMesh``: tests resolve sharding
    rules against it without devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(names))


def _resolve(spec: Sequence[Any], mesh: Mesh) -> P:
    """Map logical axis names to mesh axes present on *mesh*."""
    table = _axis_table()
    out = []
    for ax in spec:
        mesh_axes = table.get(ax, (ax,) if ax else None)
        if mesh_axes is None:
            out.append(None)
            continue
        present = tuple(a for a in mesh_axes if a in mesh.axis_names)
        out.append(present if len(present) > 1 else (present[0] if present else None))
    return P(*out)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def fit_pspec(shape: tuple, spec: P, mesh: Mesh, *, relocate: bool = True,
              min_relocate_bytes: int = 0) -> P:
    """Make a PartitionSpec legal for *shape*: pjit argument shardings
    require exact divisibility (no GSPMD padding at the jit boundary), so
    non-dividing assignments are moved to the largest divisible unassigned
    dim (relocate=True) or dropped.

    min_relocate_bytes: skip relocation for small tensors -- replicating a
    9 MB attention projection is free, while relocating it to its *input*
    dim turns every consumer matmul into a partial-sum + all-reduce
    (EXPERIMENTS.md §Perf iteration A4)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    entries = entries[:len(shape)]
    # dedup: a mesh axis may appear once; keep the first (leftmost) use so
    # specs can express fallbacks like ("expert", ..., "model") where both
    # resolve to the model axis and only one survives
    used: set = set()
    for i, e in enumerate(entries):
        if e is None:
            continue
        axes = e if isinstance(e, tuple) else (e,)
        if any(a in used for a in axes):
            entries[i] = None
            continue
        if shape[i] % _axis_size(mesh, e) == 0:
            used.update(axes)
    homeless = []
    for i, e in enumerate(entries):
        if e is not None and shape[i] % _axis_size(mesh, e) != 0:
            homeless.append(e)
            entries[i] = None
    if relocate and min_relocate_bytes:
        elems = 1
        for d in shape:
            elems *= d
        if elems * 4 < min_relocate_bytes:
            relocate = False
    if relocate:
        placed: set = set()
        for cur in entries:
            if cur is not None:
                placed.update(cur if isinstance(cur, tuple) else (cur,))
        for e in homeless:
            axes = e if isinstance(e, tuple) else (e,)
            if any(a in placed for a in axes):
                continue            # fallback entry already claimed this axis
            cand = [i for i, (d, cur) in enumerate(zip(shape, entries))
                    if cur is None and d % _axis_size(mesh, e) == 0
                    and d >= _axis_size(mesh, e)]
            if cand:
                best = max(cand, key=lambda i: shape[i])
                entries[best] = e
                placed.update(axes)
    return P(*entries)


def constrain(x: jax.Array, *spec: Any) -> jax.Array:
    """with_sharding_constraint against the active mesh; no-op when none."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(spec) < x.ndim:
        spec = tuple(spec) + (None,) * (x.ndim - len(spec))
    fitted = fit_pspec(x.shape, _resolve(spec, mesh), mesh, relocate=False)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


# ---------------------------------------------------------------------------
# Parameter sharding rules.  Key -> PartitionSpec tail over the *trailing*
# dims of the leaf (leading layer-stack dims padded with None).
# ---------------------------------------------------------------------------
SHARDING_RULES: dict[str, tuple] = {
    # embeddings / output head: vocab over model
    "embed": ("model", None),
    "lm_head": (None, "model"),
    # attention: heads over model
    "wq": (None, "model", None),          # (D, Hq, hd)
    "wk": (None, "model", None),          # (D, Hkv, hd)
    "wv": (None, "model", None),
    "wo": ("model", None, None),          # (Hq, hd, D)
    # MLA (deepseek): low-rank kv path; shard the decompression over heads
    "w_dq": (None, None),                 # (D, q_lora) -- small, replicated
    "w_uq": (None, "model", None),        # (q_lora|D, Hq, qk_head)
    "w_dkv": (None, None),                # (D, kv_lora + rope) replicated (small)
    "w_uk": (None, "model", None),        # (kv_lora, Hq, qk_nope)
    "w_uv": (None, "model", None),        # (kv_lora, Hq, v_head)
    "w_qr": (None, "model", None),        # rope-part q proj
    # MLP
    "w_gate": (None, "model"),            # (D, F)
    "w_up": (None, "model"),
    "w_down": ("model", None),            # (F, D)
    # MoE: experts over model axis (expert-parallel)
    "router": (None, None),               # (D, E) small, replicated
    "experts_gate": ("expert", None, None),   # (E, D, F)
    "experts_up": ("expert", None, None),
    "experts_down": ("expert", None, None),   # (E, F, D)
    "shared_gate": (None, "model"),
    "shared_up": (None, "model"),
    "shared_down": ("model", None),
    # SSM / xLSTM: inner dim over model
    "in_proj": (None, "model"),           # (D, inner)
    "out_proj": ("model", None),          # (inner, D)
    "conv_w": (None, "model"),            # (k, inner)
    "conv_b": ("model",),
    "xbc_proj": (None, "model"),
    "dt_proj": (None, "model"),
    "A_log": ("model",),
    "D_skip": ("model",),
    "gate_proj": (None, "model"),
    "ssm_norm": ("model",),
    # sLSTM / mLSTM gates
    "w_i": (None, "model"), "w_f": (None, "model"), "w_o": (None, "model"),
    "w_z": (None, "model"), "w_qx": (None, "model"), "w_kx": (None, "model"),
    "w_vx": (None, "model"),
    "r_i": (None, None), "r_f": (None, None), "r_o": (None, None), "r_z": (None, None),
    # norms / scalars: replicated
    "scale": (None,), "bias": (None,), "b_i": (None,), "b_f": (None,),
    "b_o": (None,), "b_z": (None,), "alpha": (None,),
    # conv stubs / lenet
    "w": None, "b": None,
}


def leaf_spec(path: tuple, leaf: Any) -> tuple:
    """PartitionSpec entries for one param leaf, from its dict key."""
    key = None
    for p in reversed(path):
        name = getattr(p, "key", getattr(p, "name", None))
        if isinstance(name, str):
            key = name
            break
    rule = SHARDING_RULES.get(key)
    ndim = jnp.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim
    if rule is None:
        return (None,) * ndim
    rule = tuple(rule)
    if len(rule) > ndim:            # e.g. scalar stored where rule expects vector
        return (None,) * ndim
    return (None,) * (ndim - len(rule)) + rule


def param_pspecs(params: Any) -> Any:
    """Tree of PartitionSpec (logical names) mirroring *params*."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: P(*leaf_spec(path, leaf)), params
    )


def param_shardings(params: Any, mesh: Mesh, *,
                    min_relocate_bytes: int = 0) -> Any:
    """Tree of NamedSharding for *params* on *mesh* (resolving logical axes,
    fitted to divisibility with relocation to the largest divisible dim;
    tensors under min_relocate_bytes replicate instead of relocating)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, fit_pspec(tuple(leaf.shape), _resolve(leaf_spec(path, leaf), mesh),
                            mesh, min_relocate_bytes=min_relocate_bytes)),
        params,
    )
