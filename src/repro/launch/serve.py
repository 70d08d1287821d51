"""Serving launcher: batched greedy generation through the KServe analog.

`python -m repro.launch.serve --arch zamba2-1.2b --requests 32` spins up an
InferenceService whose predictor runs prefill + a greedy decode loop on the
reduced config, then runs the paper's stress test against it.
"""
from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from ..clouds.profiles import get_profile
from ..configs import registry
from ..models import lm, steps
from ..serving.kserve import InferenceService, Predictor
from ..telemetry.events import EventLog
from .compile_cache import use_compile_cache


def make_lm_predictor(cfg, *, gen_tokens: int = 8, prompt_len: int = 16,
                      seed: int = 0) -> Predictor:
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    cache_len = prompt_len + gen_tokens + 1

    @jax.jit
    def predict(tokens):
        batch = {"tokens": tokens}
        if cfg.use_mrope:
            b, s = tokens.shape
            batch["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, None], (b, 3, s))
        if cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (tokens.shape[0], min(cfg.n_vision_tokens, tokens.shape[1]),
                 cfg.d_model), cfg.compute_dtype)
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (tokens.shape[0], cfg.encoder_len, cfg.d_model), cfg.compute_dtype)
        last, cache = steps.prefill(params, batch, cfg=cfg, cache_len=cache_len)
        first = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
        start = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
        if cfg.use_mrope:
            start = jnp.broadcast_to(start[:, None], (tokens.shape[0], 3))
        toks, _ = steps.greedy_decode_loop(params, cache, first, start,
                                           gen_tokens, cfg=cfg)
        return toks

    example = np.zeros((1, prompt_len), np.int32)
    return Predictor(cfg.name, predict, example)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--profile", default="gcp")
    ap.add_argument("--strategy", default="kserve",
                    choices=("baremetal", "k8s", "kserve"))
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=8)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = registry.get_smoke_config(args.arch)
    pred = make_lm_predictor(cfg, gen_tokens=args.gen_tokens)
    log = EventLog()
    svc = InferenceService(pred, get_profile(args.profile), args.strategy,
                           max_batch=args.max_batch, log=log)
    res = svc.stress_test(args.requests)
    print(json.dumps(res.summary(), indent=1))


if __name__ == "__main__":
    main()
