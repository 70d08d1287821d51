"""Benchmark driver: one module per paper table + roofline + kernels.
Prints ``name,us_per_call,derived`` CSV (spec'd output format).

  python -m benchmarks.run [--only katib|inference|pipeline|roofline|kernels]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import bench_gateway, bench_inference, bench_katib, bench_kernels, \
    bench_pipeline, bench_roofline
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "inference": bench_inference.run,     # paper Table 3 / Fig 21
    "gateway": bench_gateway.run,         # model-mesh fleet (beyond paper)
    "pipeline": bench_pipeline.run,       # paper Tables 4+5 / Figs 22-23
    "katib": bench_katib.run,             # paper Table 2 / Fig 20
    "roofline": bench_roofline.run,       # deliverable (g)
    "kernels": bench_kernels.run,         # kernel microbench
}


def main(argv=None) -> int:
    """Runs the suites; returns 1 if any suite raised, else 0."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=list(SUITES), default=None)
    args = ap.parse_args(argv)
    suites = {args.only: SUITES[args.only]} if args.only else SUITES
    use_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # keep the harness running table-per-table
            traceback.print_exc()
            print(f"{name}_SUITE_ERROR,-1,{type(e).__name__}:{str(e)[:80]}",
                  flush=True)
            failed.append(name)
            continue
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.2f},{derived}", flush=True)
        print(f"# suite {name} finished in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if failed:
        print(f"# failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
