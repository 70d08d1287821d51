"""Continuous-batching LLM serving: more requests than decode slots, with
admission into freed slots mid-flight (vLLM-style scheduling on the same
decode path the dry-run lowers).

Prompts ingest through the batched-prefill path (--prefill-chunk C:
ceil(P/C) flash-attention prefill calls write the KV rows directly, then
the request enters the decode slot pool); tests/test_prefill_oracle.py
holds it to the teacher-forced decode reference.

    PYTHONPATH=src python examples/continuous_batching.py --arch h2o-danube-3-4b
"""
import argparse
import json
import time

import jax
import numpy as np

from repro.configs import registry
from repro.models import lm
from repro.serving.continuous import ContinuousBatcher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens a prefill call (>= 1)")
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, args.prompt_len).tolist()
               for _ in range(args.requests)]

    cb = ContinuousBatcher(cfg, params, max_slots=args.slots, max_len=96,
                           prefill_chunk=args.prefill_chunk)
    reqs = [cb.submit(list(p), max_new=args.max_new) for p in prompts]
    t0 = time.perf_counter()
    done = cb.run()
    wall = time.perf_counter() - t0
    assert len(done) == args.requests
    summary = {
        "arch": cfg.name,
        "requests": args.requests,
        "slots": args.slots,
        "prefill_chunk": args.prefill_chunk,
        "prefill_stats": dict(cb.prefill_stats),
        "engine_steps": cb.step_count,
        "wall_s": round(wall, 2),
        "tokens_generated": sum(len(r.output) for r in reqs),
        "admission_steps": [r.admitted_step for r in reqs],
        # from the requests' own clock times (time.perf_counter())
        "queue_wait_ms": [round((r.t_admit - r.t_submit) * 1e3, 2) for r in reqs],
        "ttft_ms": [round((r.t_first - r.t_submit) * 1e3, 2) for r in reqs],
        "sample_output": reqs[0].output,
    }
    print(json.dumps(summary, indent=1))


if __name__ == "__main__":
    main()
