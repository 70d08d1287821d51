"""The profiler trace of a run's window, reduced to numbers.

`Recorder` starts JAX's profiler before the window and stops it after; the
harness wraps each of its calls into the program in a TraceAnnotation
named "bench.<what>", and the whole window in "bench.window".
`events_from_xplane` reads the trace into plain tuples and `reduce` turns
those into:

  busy_s / window_s    union of the intervals in which an operation ran on
                       each device, clipped to the window, averaged over
                       the devices
  device_ops           the operations that took most device time, each
                       by its own time (a loop's body is not counted twice)
  idle_gaps            the longest gaps with no operation on a device, each
                       labelled by the innermost harness span around it
  collective_s         time in collectives, and the part of it during which
  exposed_collective_s no other operation ran on that device

The reduction works on plain tuples so that a test can check it against
totals worked out by hand.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|alltoall", re.I)


class Recorder:
    """Profiler on for the window only; the trace goes to a temporary
    directory that `read` removes."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)

    def read(self) -> dict:
        self._jax.profiler.stop_trace()
        try:
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return events_from_xplane(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def events_from_xplane(path: str) -> dict:
    """{"devices": {plane: [(op, start_ns, dur_ns), ...]},
        "spans": [(name, start_ns, dur_ns), ...]}

    Device operations are the events of each "/device:" plane's "XLA Ops"
    line.  Where the backend has no device plane (the CPU), the host events
    that carry an `hlo_op` stat stand in, so that a rehearsal exercises the
    same reduction.  Spans are the host events named "bench.*"."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    host_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif (line.name.startswith("tf_XLA") and e.duration_ns > 0
                          and any(k == "hlo_op" for k, _ in e.stats)):
                        host_ops.append((e.name, e.start_ns, e.duration_ns))
    if not devices and host_ops:
        devices["/host:CPU (XLA ops)"] = host_ops
    return {"devices": devices, "spans": spans}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _subtract(a, b):
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def op_name(event_name: str) -> str:
    """A device event's short name: the HLO instruction's name and, where
    it has one, its result's shape without the layout, as in
    "fusion.12 bf16[8,10240]"."""
    name, sep, rest = event_name.partition(" = ")
    name = name.lstrip("%")
    if sep and rest and rest[0] != "(":
        name += " " + rest.split("{", 1)[0].split(" ", 1)[0]
    return name


def _self_times(clip):
    """(op, seconds of its own) for intervals that may nest: an operation
    that holds others (a loop and its body) keeps only the time they leave."""
    out, stack = [], []
    for s, e, n in sorted(clip, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, n, 0.0])
    out.extend(stack)
    return [(n, max(e - s - kids, 0.0)) for s, e, n, kids in out]


def _label(spans, t: float) -> str:
    """Innermost (shortest) harness span holding time t, else "outside"."""
    best = None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0][len(SPAN_PREFIX):] if best else "outside"


def reduce(events: dict, top: int = 10) -> dict:
    """Numbers of the window; see the module docstring.  Times in seconds."""
    windows = [(s, s + d) for n, s, d in events["spans"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = windows[0]
    spans = [x for x in events["spans"] if x[1] < w1 and x[1] + x[2] > w0]
    busy, coll, exposed = [], [], []
    ops: dict = {}
    gaps: list = []
    for dev, evs in events["devices"].items():
        clip = [(max(s, w0), min(s + d, w1), n) for n, s, d in evs
                if s < w1 and s + d > w0]
        for n, t in _self_times(clip):
            k = op_name(n)
            ops[k] = ops.get(k, 0.0) + t
        all_u = _union((s, e) for s, e, _ in clip)
        c_u = _union((s, e) for s, e, n in clip if COLLECTIVE.search(n))
        other = _union((s, e) for s, e, n in clip if not COLLECTIVE.search(n))
        busy.append(_length(all_u))
        coll.append(_length(c_u))
        exposed.append(_length(_subtract(c_u, other)))
        for s, e in _subtract([[w0, w1]], all_u):
            gaps.append((_label(spans, (s + e) / 2), e - s))
    n = max(len(busy), 1)
    ns = 1e-9
    return {
        "devices": len(busy),
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / n * ns,
        "collective_s": sum(coll) / n * ns,
        "exposed_collective_s": sum(exposed) / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps, key=lambda kv: -kv[1])[:top]],
    }
