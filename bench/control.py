"""Readings that set a cell's limits: the program's number and the
control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

Each seed is one run of the cell as bench/run.py makes it (set-up, window,
reference), with the control beside it: the reference in the precision
below the configuration's (float8 for bfloat16), whose own first choice at
each position is read against the float32 reference.  One line per seed:

    {"seed", "value", "control", "compiles"}

The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = harness.Cell(args.workload)
    devs = harness.require_devices(cell.chips)
    use_compile_cache()
    for seed in args.seeds:
        run = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        counter = harness.CompileCounter()
        try:
            rec = cell.driver.run(cell, run, devs, time.perf_counter(), counter,
                                  control=True)
        finally:
            counter.close()
        (name, check), = rec["checks"].items()
        print(json.dumps({"seed": seed, "check": name, "value": check["value"],
                          "control": rec["control_gap"],
                          "compiles": rec["compiles"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
