"""program_trace.py's traced run, with the op names of the program served.

`program_trace.decode_op_names` compiles `lm.decode_step` under a plain
jit.  ContinuousBatcher serves `continuous.decode_program(cfg)`, which
donates the cache, so the served program's instructions can carry other
names, and scopes read through the plain map then misattribute device
time.  This script makes the same traced run and reads its scopes through
the served program's own map, compiled on shapes placed on the first
device as the batcher's arrays are:

    python3 bench/served_trace.py --workload h2o.batch_decode --seed <n> --seconds 30

It prints what program_trace.py prints, and on standard error the seconds
that the run (as bench/run.py --trace 1 makes it), the map's compile and
the reduction each took.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_trace  # noqa: E402


def served_decode_hlo(cfg, max_slots: int, max_len: int) -> str:
    """The compiled text of the batcher's decode program for `cfg` at
    `max_slots` x `max_len`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import lm
    from repro.serving import continuous
    dev = SingleDeviceSharding(jax.devices()[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)

    params = place(jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: lm.init_cache(cfg, max_slots, max_len)))
    tok = jax.ShapeDtypeStruct((max_slots, 1), jnp.int32, sharding=dev)
    pos = jax.ShapeDtypeStruct((max_slots,), jnp.int32, sharding=dev)
    return continuous.decode_program(cfg).lower(params, cache, tok, pos) \
        .compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness, tracing
    from bench import run as bench_run

    def took(what, t0):
        print(f"served_trace: {what} {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)

    cell = harness.Cell(args.workload)
    kept: dict = {}
    read_old = tracing.events_from_xplane

    def read_both(path):
        kept.update(program_trace.events_from_xplane(path))
        return read_old(path)

    t0 = time.perf_counter()
    tracing.events_from_xplane = read_both     # the harness's Recorder reads through it
    try:
        out = bench_run.measure(cell, argparse.Namespace(
            seed=args.seed, seconds=args.seconds, trace=1))
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        tracing.events_from_xplane = read_old
    took("traced run", t0)
    harness.emit(out["result"], out["checks"])
    t0 = time.perf_counter()
    sv = cell.config["serving"]
    names = program_trace.hlo_op_names(served_decode_hlo(
        harness.program_config(cell.config), sv["max_slots"], sv["max_len"]))
    took("op-name compile", t0)
    t0 = time.perf_counter()
    r = program_trace.reduce(kept, names)
    took("reduction", t0)
    print(json.dumps({"numbers": program_trace.numbers(r), **{k: r[k] for k in (
        "window_s", "busy_s", "idle_labels", "idle_gaps", "spans", "modules",
        "scopes", "step_host_s")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
