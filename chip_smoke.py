"""Chip smoke: the serving path at published widths on one TPU, and sharded
training across four.

    python chip_smoke.py                # one chip: h2o-danube-3-4b served
                                        # through ContinuousBatcher
    python chip_smoke.py --four-chips   # four chips: zamba2-1.2b trained on
                                        # a 2x2 ("data", "model") mesh

Weights are random, made from --seed; no checkpoint is read.  The script
runs in one process, so it alone holds the chip(s).  It exits non-zero,
printing no result, unless JAX's first device is a TPU.  Its last line of
output is one JSON object naming the device, printed only when every check
passed.  Times it prints are informational, not measurements of record.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
GB = 1e9

# Chunked prefill and lm.forward both compute in bf16 (8 significant bits, a
# relative step of 2**-8 = 3.9e-3) but sum in different orders: the prefill
# attends chunk by chunk against the cache, forward over the whole prompt at
# once.  Each of the 24 layers rounds its residual stream to bf16, so the two
# last-position logit vectors may differ by a few such steps of their scale
# (1.2% to 1.5% of max|logit| in a 24-layer, d_model 256 run on the CPU).
LOGIT_RTOL = 5e-2
# First-step losses of one model on one chip and sharded on a 2x2 mesh: the
# same bf16 math with every sharded matmul's partial sums reduced in another
# order (one all-reduce per sharded contraction).
LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(expect: str = "tpu") -> dict:
    """Print what JAX sees; raise SystemExit unless it is an `expect` device."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"devices: {devs}")
    log(f"platform={d.platform} device_kind={d.device_kind} count={len(devs)}")
    if d.platform != expect:
        raise SystemExit(f"chip_smoke: JAX's device is {d.platform!r}, not "
                         f"{expect!r}; nothing is run on it")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def serve_phase(cfg, *, seed: int = 0, max_slots: int = 8, max_len: int = 2048,
                prefill_chunk: int = 256, n_requests: int = 16,
                prompt_lens=(256, 512, 1024), max_new: int = 32) -> dict:
    """Serve `n_requests` seeded prompts through ContinuousBatcher and check
    them; raises AssertionError on any failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serving.continuous import ContinuousBatcher

    dev = jax.devices()[0]
    log(f"serve: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} window={cfg.sliding_window} "
        f"param_dtype={cfg.param_dtype} dtype={cfg.dtype}")

    params_b = _tree_bytes(jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg)))
    cache_b = _tree_bytes(jax.eval_shape(
        lambda: lm.init_cache(cfg, max_slots, max_len)))
    row_b = _tree_bytes(jax.eval_shape(lambda: lm.init_cache(cfg, 1, max_len)))
    # the batcher donates the cache to its decode step, which writes it in
    # place; prefill works on a one-row cache of its own
    need = params_b + cache_b + row_b
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    share = f"{need / limit:.1%} of the {limit / GB:.2f} GB limit" if limit \
        else "device reports no limit"
    log(f"serve: max_slots={max_slots} max_len={max_len} "
        f"prefill_chunk={prefill_chunk}, chosen so that params "
        f"{params_b / GB:.2f} GB + cache {cache_b / GB:.2f} GB + one-row "
        f"cache {row_b / GB:.2f} GB = {need / GB:.2f} GB stays under 90% of "
        f"HBM ({share})")
    if limit:
        assert need <= 0.9 * limit, "batcher size leaves under 10% of HBM free"

    t0 = time.perf_counter()
    params = jax.jit(lm.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    log(f"serve: init_params {time.perf_counter() - t0:.2f} s (compile included)")

    rng = np.random.default_rng(seed)
    lens = rng.choice(np.asarray(prompt_lens), n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist() for n in lens]
    cb = ContinuousBatcher(cfg, params, max_slots=max_slots, max_len=max_len,
                           prefill_chunk=prefill_chunk)

    # warm-up: one short request compiles the prefill and decode programs
    check = prompts[0]
    t0 = time.perf_counter()
    warm = cb.submit(check, 2)
    cb.run()
    log(f"serve: warm-up request {time.perf_counter() - t0:.2f} s "
        f"(compiles prefill and decode)")

    # chunked prefill vs one full-sequence forward on the same prompt
    pf_logits, _ = cb.prefill(check)
    fwd = jax.jit(lambda p, t: lm.forward(p, cfg, {"tokens": t})[0][0, -1])
    ref = np.asarray(fwd(params, jnp.asarray(check, jnp.int32)[None]),
                     np.float32)
    got = np.asarray(pf_logits, np.float32)
    assert np.isfinite(got).all(), "prefill logits are not finite"
    assert np.isfinite(ref).all(), "forward logits are not finite"
    err = float(np.max(np.abs(got - ref)))
    tol = LOGIT_RTOL * float(np.max(np.abs(ref)))
    top = int(np.argmax(ref))
    first = warm.output[0]
    margin = float(ref[top] - np.partition(ref, -2)[-2])
    log(f"serve: prefill vs forward on a {len(check)}-token prompt: max|diff| "
        f"{err:.4g}, tolerance {tol:.4g} ({LOGIT_RTOL} x max|logit|); first "
        f"token {first}, forward argmax {top} (top-2 margin {margin:.4g})")
    assert err <= tol, "prefill logits differ from forward beyond tolerance"
    # where forward's own top two are closer than the tolerance, bf16 cannot
    # order them: the token must then be one within tolerance of the top
    assert first == top or (margin <= tol and ref[top] - ref[first] <= tol), \
        "first generated token disagrees with forward's argmax"

    for p in prompts:
        cb.submit(p, max_new)
    steps0 = cb.step_count
    t0 = time.perf_counter()
    done = cb.run()
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.output) for r in done)
    assert len(done) == n_requests, f"{len(done)} of {n_requests} finished"
    for r in done:
        assert len(r.output) == max_new, (r.rid, len(r.output))
        assert all(0 <= t < cfg.vocab_size for t in r.output), r.rid
    peak = _peak_bytes(dev)
    log(f"serve: {n_requests} requests (prompt lengths "
        f"{sorted(lens.tolist())}) drained in {wall:.2f} s, "
        f"{cb.step_count - steps0} steps, {n_tok} tokens generated; "
        f"peak_bytes_in_use "
        f"{'n/a' if peak is None else f'{peak / GB:.2f} GB'} (informational)")
    return {"requests": len(done), "tokens": n_tok, "logit_err": err,
            "logit_tol": tol}


def four_chip_phase(cfg_full, cfg_cut, *, batch: int = 4, seq: int = 128,
                    steps: int = 3, seed: int = 0) -> dict:
    """LMTrainJob on a 2x2 ("data", "model") mesh at cfg_full, then cfg_cut
    on one chip and on the mesh with first-step losses compared; raises
    AssertionError on any failed check."""
    import jax

    from repro.core.trainjob import LMTrainJob
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    assert len(devs) >= 4, f"four-chip phase needs 4 devices, has {len(devs)}"
    mesh = make_mesh((2, 2), ("data", "model"))

    def train(cfg, mesh, what):
        t0 = time.perf_counter()
        hist = LMTrainJob(cfg, batch_size=batch, seq_len=seq, n_steps=steps,
                          seed=seed, mesh=mesh).run()["history"]
        log(f"train: {what}: {cfg.name} layers={cfg.n_layers} "
            f"d_model={cfg.d_model} batch={batch} seq={seq}: losses "
            f"{[round(x, 5) for x in hist]} in {time.perf_counter() - t0:.2f} s "
            f"(compile included)")
        assert len(hist) == steps and all(map(math.isfinite, hist)), hist
        return hist

    train(cfg_full, mesh, "2x2 mesh")
    peaks = [_peak_bytes(d) for d in devs[:4]]
    log("train: peak_bytes_in_use per device after the full-width run: "
        + ", ".join("n/a" if p is None else f"{d.id}: {p / GB:.2f} GB"
                    for d, p in zip(devs, peaks)))
    if None not in peaks:
        assert min(peaks) >= 0.5 * max(peaks), "training state piled on one device"

    one = train(cfg_cut, None, "one chip")[0]
    four = train(cfg_cut, mesh, "2x2 mesh")[0]
    rel = abs(one - four) / abs(one)
    log(f"train: first-step loss one chip {one:.6f} vs 2x2 mesh {four:.6f}: "
        f"relative difference {rel:.3g}, tolerance {LOSS_RTOL}")
    assert rel <= LOSS_RTOL, "sharded loss disagrees with one-chip loss"
    return {"loss_one": one, "loss_mesh": four, "peaks": peaks}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_info()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import registry
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    if args.four_chips:
        full = registry.get_config("zamba2_1_2b")
        # 12 of 38 layers: two groups of six mamba2 layers, each followed by
        # the shared attention block; fp32 state fits one chip
        four_chip_phase(full, full.replace(n_layers=12), seed=args.seed)
    else:
        # bf16 weights: fp32 weights alone (15.9 GB) fill the 16 GB chip
        cfg = registry.get_config("h2o_danube_3_4b").replace(
            param_dtype="bfloat16")
        serve_phase(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
