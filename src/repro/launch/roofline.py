"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds.  jax lowers to the
per-device (post-SPMD-partitioning) module, so cost_analysis() FLOPs/bytes
and the HLO collective shapes are ALREADY per-chip quantities:
  compute    = HLO_FLOPs_per_chip  / peak_FLOP/s
  memory     = HLO_bytes_per_chip  / HBM_bw
  collective = collective_bytes_per_chip / link_bw
(equivalently total/(chips*rate) -- the assignment's formula -- since
total = chips * per-chip for an evenly sharded program).

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis().  Collective bytes
are NOT in cost_analysis: we parse the optimized HLO text and sum the
*output* buffer sizes of every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute (async *-start ops counted once, -done
skipped).  Output-bytes is a consistent per-op traffic proxy (ring
all-reduce moves ~2x this; documented convention, same across all combos).

The MODEL_FLOPS / (HLO_FLOPs * chips) ratio reports how much of the
compiled compute is "useful" -- GSPMD padding waste, remat recompute and
softmax/normalisation overhead all push it away from ~1.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

from ..clouds.profiles import HardwareSpec, TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def cost_dict(compiled) -> dict:
    """compiled.cost_analysis() across jax versions: 0.4.x returns a
    one-element list of per-program dicts, newer releases the dict itself."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


# ops whose outputs genuinely travel to HBM even under TPU fusion; the
# elementwise/broadcast/select/convert chains around them fuse away on TPU
# (the CPU backend, which compiles this dry-run, fuses far less -- so raw
# "bytes accessed" is a fusion-naive upper bound; this models the TPU view)
_MATERIALIZING = {
    "dot", "convolution", "reduce", "reduce-window", "scatter", "gather",
    "sort", "concatenate", "copy", "transpose", "dynamic-update-slice",
    "dynamic-slice", "pad", "select-and-scatter", "rng", "cholesky",
    "triangular-solve", "fft", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "while", "custom-call",
}

_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.-]+)\s*(?:\([^)]*\))?\s*->.*{\s*$")
_OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(.+?)\s*([\w-]+)\(")


def fusion_modeled_bytes(hlo_text: str) -> int:
    """Bytes that still hit HBM assuming TPU-grade elementwise fusion:
    ENTRY parameters (weights/activations read once) + outputs of
    materialising ops in non-fusion computations.  Fusion subcomputations
    are skipped entirely (their 'parameter' lines duplicate producer
    buffers); `fusion` op outputs ARE counted (the fused kernel's single
    write)."""
    total = 0
    in_fusion = False
    in_entry = False
    depth = 0
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR.match(line.strip()) if line.strip().endswith("{") else None
        if hdr and depth == 0:
            name = hdr.group(2)
            in_fusion = "fused" in name or "region" in name
            in_entry = bool(hdr.group(1))
            depth = 1
            continue
        if depth and line.strip() == "}":
            depth = 0
            in_fusion = in_entry = False
            continue
        if not depth or in_fusion:
            continue
        m = _OP_LINE.match(line)
        if not m:
            # entry parameters: "%p = f32[..] parameter(0)" matches _OP_LINE;
            # nothing else to do here
            continue
        type_part, op = m.groups()
        if op == "parameter":
            if in_entry:
                total += _shape_bytes(type_part)
            continue
        if op == "fusion" or op in _MATERIALIZING:
            total += _shape_bytes(type_part)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-buffer bytes per collective kind from optimized HLO."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        lhs, _, rhs = line.partition("=")
        m = re.match(r"\s*([\w.-]+)\s*\(?", rhs.strip())
        # find op name: first token after the output type annotation
        op = None
        for kind in COLLECTIVES:
            if re.search(rf"\b{kind}(-start)?\(", rhs):
                if re.search(rf"\b{kind}-done\(", rhs):
                    op = None
                else:
                    op = kind
                break
        if op is None:
            continue
        # output type(s) are between '=' and the op name
        type_part = rhs.split(op)[0]
        out[op] += _shape_bytes(type_part)
        counts[op] += 1
    out_total = sum(out.values())
    return {"per_kind_bytes": out, "per_kind_counts": counts, "total_bytes": out_total}


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def total_s(self) -> float:
        # terms overlap on real hardware; max() is the roofline bound
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "dominant": self.dominant,
                "bound_s": self.total_s}


def roofline(flops: float, bytes_accessed: float, coll_bytes: float,
             chips: int, hw: HardwareSpec = TPU_V5E) -> RooflineTerms:
    """Inputs are per-chip (the lowered module is the per-device program)."""
    return RooflineTerms(
        compute_s=flops / hw.peak_flops_bf16,
        memory_s=bytes_accessed / hw.hbm_bw,
        collective_s=coll_bytes / hw.ici_bw,
        flops=flops, bytes_accessed=bytes_accessed, coll_bytes=coll_bytes,
        chips=chips,
    )


def chunk_scan_correction_flops(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Analytic add-back for the rolled time-chunk scans (mamba2 SSD /
    mLSTM) in the dry-run.  With layer scans unrolled, HLO counts ONE chunk
    body per layer, i.e. total/nc -- so we add total*(nc-1)/nc.  Per-layer
    forward flops (matmul terms only):
      SSD    ~ 2BST(N + H*P) + 4BSHPN
      mLSTM  ~ 6BSTHD + 6BSHD^2
    Train counts fwd+bwd (x3)."""
    if cfg.family not in ("ssm", "hybrid") or shape_kind == "decode":
        return 0.0
    B, S, T = batch, seq, cfg.ssm_chunk
    nc = max(-(-S // T), 1)
    if nc <= 1:
        return 0.0
    mult = 3.0 if shape_kind == "train" else 1.0
    if cfg.family == "hybrid":                      # zamba2: mamba2 layers
        H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        per_layer = 2 * B * S * T * (N + H * P) + 4 * B * S * H * P * N
        n_layers = cfg.n_layers
    else:                                           # xlstm: mLSTM layers
        H = cfg.n_heads
        D = cfg.d_model // H
        per_layer = 6 * B * S * T * H * D + 6 * B * S * H * D * D
        n_layers = cfg.n_layers - (cfg.n_layers // cfg.slstm_every
                                   if cfg.slstm_every else 0)
    return mult * n_layers * per_layer * (nc - 1) / nc


def slstm_correction_flops(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Analytic add-back for the sLSTM time scan, the one loop the dry-run
    cannot unroll (S sequential steps).  Covers the in-loop recurrent
    matmuls (4 gates x per-head hd x hd); input projections are outside the
    loop and already counted by HLO.  Train counts fwd+bwd (x3)."""
    if cfg.family != "ssm" or not cfg.slstm_every or shape_kind == "decode":
        return 0.0
    n_slstm = cfg.n_layers // cfg.slstm_every
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    per_token = 4 * h * hd * hd * 2
    mult = 3.0 if shape_kind == "train" else 1.0
    return mult * n_slstm * batch * seq * per_token


def model_flops(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D for a
    forward-only token pass (prefill/decode)."""
    n_active = cfg.approx_active_params()
    mult = 6.0 if shape_kind == "train" else 2.0
    tokens = batch * (1 if shape_kind == "decode" else seq)
    return mult * n_active * tokens
