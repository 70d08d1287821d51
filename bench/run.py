"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell's entry in BENCHMARK.json names its
configuration and traffic files; see bench/harness.py for how they are
found.  The run loads, warms up every shape the window will use, measures
for --seconds, checks what the window served against the plain reference,
and prints one JSON object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "checks"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read from a profiler trace of the window
and the harness's own clock.  The run exits non-zero and prints no result
unless JAX's devices are TPUs, as many as the cell asks for, and when any
program is compiled inside the window.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def measure(cell, args, platform: str = "tpu") -> dict:
    """Run the cell once; the result line's fields and its checks."""
    import jax
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    devs = harness.require_devices(cell.chips, platform)
    kind = devs[0].device_kind
    peaks = harness.peaks(kind, cell.base / "peaks.json")
    harness.log(f"devices: {cell.chips} x {devs[0].platform} {kind}; "
                f"compile cache {use_compile_cache()}")
    # cache every program, however quick to compile, so that set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    counter = harness.CompileCounter()
    try:
        rec = cell.driver.run(cell, args, devs, T_PROC, counter)
    finally:
        counter.close()
    if rec["compiles"]:
        raise harness.BenchError(f"{rec['compiles']} programs were compiled "
                                 f"inside the window: {counter.names}")
    rec["peaks"] = peaks
    metrics = {}
    for m in cell.metrics(args.trace):
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = rec["checks"]
    correct = rec["failed"] == 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": rec["memory_peak"]}
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    tr = rec["trace"]
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
        harness.log(f"trace: {tr['devices']} device(s), collectives "
                    f"{tr['collective_s']} s, exposed {tr['exposed_collective_s']} s")
    harness.log(f"memory_peak_bytes {rec['memory_peak']}; compiles in window "
                f"{rec['compiles']}")
    return {"result": out, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.Cell(args.workload)
        out = measure(cell, args)
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    harness.emit(out["result"], out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
