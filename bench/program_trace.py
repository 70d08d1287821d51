"""What the program records inside a traced window, reduced to numbers.

`bench/tracing.py` reduces a trace to what the harness sees from outside:
device busy time, the heaviest operations, and idle gaps labelled by the
harness's own "bench.*" spans.  ContinuousBatcher (serving/continuous.py)
records spans of its own, named "serve.*", names its programs
(jit_serve_decode, jit_serve_prefill), and the model names the parts of a
layer inside them (jax.named_scope "attention", "ffn", ...).  This module
reads those from the same trace:

  events_from_xplane(path)
      {"devices": {plane: [(op, start_ns, dur_ns, module), ...]},
       "spans": [(name, start_ns, dur_ns, args), ...]}
      spans are the host events named "bench.*" or "serve.*"; `module` is
      the program (HLO module) an operation belongs to

  decode_op_names(cell)
      {instruction: name-scope path} of the cell's decode program, from
      its compiled HLO: a TPU trace's operations carry no scope, so an
      operation's scope is looked up by its instruction's name

  reduce(events, op_names)
      every key of tracing.reduce, computed by it on the same events, with
      idle_gaps    each labelled by the innermost span of either kind, the
                   program's with their full "serve." name
      idle_labels  idle seconds of the window by that label
      spans        {name: {"count", "total_s", "self_s"}} of the "serve.*"
                   spans, clipped to the window
      modules      {module: device seconds}: the union of its operations,
                   averaged over the devices
      scopes       {scope: device self seconds} of jit_serve_decode's
                   operations, by the top-level named scope they lie in
      step_host_s  mean over the serve.step spans that hold no serve.admit
                   of the span less its serve.step.sync: the host's own
                   time in a decode step

  numbers(r)   the per-layer numbers read from a reduction (below)

Run as a script, it makes one traced run of a cell, exactly as
`bench/run.py --trace 1` does, compiles the decode program once more for
its op names, and prints the run's result line and then, as the last
line, these numbers:

    python3 bench/program_trace.py --workload h2o.batch_decode --seed <n> --seconds 30
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracing  # noqa: E402

PROGRAM_PREFIX = "serve."
DECODE_MODULE = "jit_serve_decode"
NO_SCOPE = "(none)"
# components of a name-scope path that JAX adds for control flow and calls
_NOT_SCOPES = {"while", "body", "cond", "closed_call"}
_IDENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
_MODULE_RUN = re.compile(r"\(\d+\)$")       # "jit_serve_decode(12)" on a TPU


def top_scope(path: str) -> str:
    """The outermost named scope of an operation's name-scope path:
    "jit(serve_decode)/while/body/closed_call/attention/dot_general" gives
    "attention"; an operation under none gives NO_SCOPE."""
    for part in path.split("/")[:-1]:
        if _IDENT.match(part) and part not in _NOT_SCOPES:
            return part
    return NO_SCOPE


def events_from_xplane(path: str) -> dict:
    """The spans and the device operations of a trace; see the module
    docstring.  An operation's module is its `hlo_module` stat (the CPU)
    or else the "XLA Modules" event around it (a TPU).  Where the backend
    has no device plane (the CPU), the host events with an `hlo_op` stat
    stand in, as in tracing.events_from_xplane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    host_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _MODULE_RUN.sub("", e.name))
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else []))
            devices[plane.name] = [
                _device_op(e.name, e.start_ns, e.duration_ns,
                           {k: v for k, v in e.stats}, runs)
                for e in lines["XLA Ops"].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((tracing.SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.append((e.name, e.start_ns, e.duration_ns,
                                      {k: v for k, v in e.stats}))
                    elif line.name.startswith("tf_XLA") and e.duration_ns > 0:
                        st = {k: v for k, v in e.stats}
                        if "hlo_op" in st:
                            host_ops.append(_device_op(e.name, e.start_ns,
                                                       e.duration_ns, st, []))
    if not devices and host_ops:
        devices["/host:CPU (XLA ops)"] = host_ops
    return {"devices": devices, "spans": spans}


def _device_op(name, start, dur, stats, runs):
    """(op, start, dur, module); `runs` are the plane's sorted (start, end,
    module) program runs."""
    module = stats.get("hlo_module")
    if module is None:
        i = bisect.bisect_right(runs, (start, float("inf"))) - 1
        module = runs[i][2] if i >= 0 and start < runs[i][1] else ""
    return (name, start, dur, str(module))


def instruction(event_name: str) -> str:
    """The HLO instruction's name of a device event: "fusion.12"."""
    return tracing.op_name(event_name).split(" ", 1)[0]


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction: op_name} from a compiled module's text.  A fusion
    whose own line carries no metadata takes the op_name of the root of the
    computation it calls."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"^\s+(ROOT )?%?([\w.\-]+) = ", line)
        if not ins:
            continue
        name = ins.group(2)
        meta = re.search(r'op_name="([^"]*)"', line)
        call = re.search(r"calls=%?([\w.\-]+)", line)
        if meta:
            own[name] = meta.group(1)
        elif call:
            calls[name] = call.group(1)
        if ins.group(1) and meta:
            roots[comp] = meta.group(1)
    for name, c in calls.items():
        if c in roots:
            own[name] = roots[c]
    return own


def _window(events):
    for n, s, d, _ in events["spans"]:
        if n == tracing.WINDOW_SPAN:
            return s, s + d
    raise ValueError("the trace holds no bench.window span")


def _labels(spans, times) -> list:
    """For each time, the innermost span of either kind holding it, else
    "outside"; harness span names lose their "bench." prefix.  Spans nest
    (one thread records them), so a sweep keeps the open ones on a stack."""
    order = sorted((x for x in spans if x[0] != tracing.WINDOW_SPAN),
                   key=lambda x: (x[1], -x[2]))
    out: dict = {}
    stack, j = [], 0
    for t in sorted(set(times)):
        while j < len(order) and order[j][1] <= t:
            stack.append(order[j])
            j += 1
        while stack and stack[-1][1] + stack[-1][2] < t:
            stack.pop()
        name = stack[-1][0] if stack else "outside"
        out[t] = name[len(tracing.SPAN_PREFIX):] \
            if name.startswith(tracing.SPAN_PREFIX) else name
    return [out[t] for t in times]


def _step_host_s(spans):
    """Mean over serve.step spans holding no serve.admit of the span less
    its serve.step.sync; None without such a step."""
    def inside(name, s, e):
        return [x for x in spans if x[0] == name and s <= x[1] and x[1] + x[2] <= e]
    host = []
    for n, s, d, _ in spans:
        if n != "serve.step" or inside("serve.admit", s, s + d):
            continue
        host.append(d - sum(x[2] for x in inside("serve.step.sync", s, s + d)))
    return sum(host) / len(host) * 1e-9 if host else None


def reduce(events: dict, op_names: dict, top: int = 10) -> dict:
    """Numbers of the window; see the module docstring.  `op_names` maps
    the decode program's instructions to their name-scope paths.  Times in
    seconds."""
    ns = 1e-9
    out = tracing.reduce({
        "devices": {k: [e[:3] for e in v] for k, v in events["devices"].items()},
        "spans": [x[:3] for x in events["spans"]
                  if x[0].startswith(tracing.SPAN_PREFIX)]}, top)
    w0, w1 = _window(events)
    spans = [x for x in events["spans"] if x[1] < w1 and x[1] + x[2] > w0]
    prog = [(max(s, w0), min(s + d, w1), n) for n, s, d, _ in spans
            if n.startswith(PROGRAM_PREFIX)]
    table: dict = {}
    for s, e, n in prog:
        t = table.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        t["count"] += 1
        t["total_s"] += (e - s) * ns
    for n, t in tracing._self_times(prog):
        table[n]["self_s"] += t * ns

    modules: dict = {}
    scopes: dict = {}
    gaps: list = []
    labels: dict = {}
    n_dev = max(len(events["devices"]), 1)
    for evs in events["devices"].values():
        clip = [(max(s, w0), min(s + d, w1), m, n) for n, s, d, m in evs
                if s < w1 and s + d > w0]
        for m in {x[2] for x in clip}:
            u = tracing._union((s, e) for s, e, mm, _ in clip if mm == m)
            modules[m] = modules.get(m, 0.0) + tracing._length(u) * ns / n_dev
        dec = [(s, e, top_scope(op_names.get(instruction(n), "")))
               for s, e, m, n in clip if m == DECODE_MODULE]
        for k, t in tracing._self_times(dec):
            scopes[k] = scopes.get(k, 0.0) + t * ns / n_dev
        idle = tracing._subtract([[w0, w1]],
                                 tracing._union((s, e) for s, e, _, _ in clip))
        for lab, (s, e) in zip(_labels(spans, [(s + e) / 2 for s, e in idle]), idle):
            gaps.append((lab, (e - s) * ns))
            labels[lab] = labels.get(lab, 0.0) + (e - s) * ns / n_dev
    out.update({
        "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda kv: -kv[1])[:top]],
        "idle_labels": dict(sorted(labels.items(), key=lambda kv: -kv[1])),
        "spans": table,
        "modules": dict(sorted(modules.items(), key=lambda kv: -kv[1])),
        "scopes": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
        "step_host_s": _step_host_s(spans),
    })
    return out


def numbers(r: dict) -> dict:
    """The per-layer numbers of the program's spans and programs, in ms;
    a number whose spans or operations the window lacks is left out.

      admit_ms             mean serve.admit span
      step_host_ms         host time of a decode step not spent waiting on
                           the device (step_host_s)
      decode_device_ms     device time of jit_serve_decode over its runs
                           (serve.step.dispatch spans)
      decode_attention_ms  device self time of jit_serve_decode under the
                           "attention" scope, over the same runs"""
    out = {}
    sp = r["spans"]
    if sp.get("serve.admit", {}).get("count"):
        out["admit_ms"] = sp["serve.admit"]["total_s"] / sp["serve.admit"]["count"] * 1e3
    if r["step_host_s"] is not None:
        out["step_host_ms"] = r["step_host_s"] * 1e3
    runs = sp.get("serve.step.dispatch", {}).get("count", 0)
    if runs and DECODE_MODULE in r["modules"]:
        out["decode_device_ms"] = r["modules"][DECODE_MODULE] / runs * 1e3
        if "attention" in r["scopes"]:
            out["decode_attention_ms"] = r["scopes"]["attention"] / runs * 1e3
    return out


def decode_op_names(cell) -> dict:
    """{instruction: op_name} of the cell's decode program, compiled again
    from its shapes: the same function and shapes as the served program,
    so the same instruction names as in the trace."""
    import jax
    import jax.numpy as jnp
    from bench import harness
    from repro.models import lm
    cfg = harness.program_config(cell.config)
    sv = cell.config["serving"]

    def serve_decode(p, c, t, pos):
        return lm.decode_step(p, cfg, t, pos, c)

    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, sv["max_slots"], sv["max_len"]))
    tok = jax.ShapeDtypeStruct((sv["max_slots"], 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((sv["max_slots"],), jnp.int32)
    return hlo_op_names(jax.jit(serve_decode).lower(params, cache, tok, pos)
                        .compile().as_text())


def traced_run(cell, seed: int, seconds: float, platform: str = "tpu"):
    """One run of `cell` with --trace 1, as bench/run.py makes it: its
    measure() output, and the reduction of its trace."""
    from bench import run as bench_run
    kept: dict = {}
    read_old = tracing.events_from_xplane

    def read_both(path):
        kept.update(events_from_xplane(path))
        return read_old(path)

    tracing.events_from_xplane = read_both     # the harness's Recorder reads through it
    try:
        out = bench_run.measure(cell, argparse.Namespace(
            seed=seed, seconds=seconds, trace=1), platform=platform)
    finally:
        tracing.events_from_xplane = read_old
    return out, reduce(kept, decode_op_names(cell))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        out, r = traced_run(harness.Cell(args.workload), args.seed, args.seconds)
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    harness.emit(out["result"], out["checks"])
    print(json.dumps({"numbers": numbers(r), **{k: r[k] for k in (
        "window_s", "busy_s", "idle_labels", "idle_gaps", "spans", "modules",
        "scopes", "step_host_s")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
