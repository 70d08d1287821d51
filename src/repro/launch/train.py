"""Training launcher: `python -m repro.launch.train --arch gemma3-4b --smoke`.

Full configs are for the dry-run mesh; on this CPU host use --smoke (the
reduced per-arch variant) or override --layers/--d-model.
"""
from __future__ import annotations

import argparse
import json

from ..checkpoint.store import ArtifactStore
from ..configs import registry
from ..core.trainjob import LMTrainJob
from ..telemetry.events import EventLog
from . import mesh as mesh_mod
from .compile_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--store", default="experiments/artifacts")
    ap.add_argument("--mesh", choices=("local", "none"), default="none")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    mesh = mesh_mod.make_local_mesh() if args.mesh == "local" else None
    log = EventLog()
    job = LMTrainJob(cfg, batch_size=args.batch, seq_len=args.seq,
                     n_steps=args.steps, lr=args.lr, mesh=mesh,
                     store=ArtifactStore(args.store), log=log)
    res = job.run(checkpoint_name=f"{cfg.name}-smoke")
    print(json.dumps({"arch": cfg.name, "loss_first": res["history"][0],
                      "loss_last": res["loss"],
                      "stages": log.totals()}, indent=1))


if __name__ == "__main__":
    main()
