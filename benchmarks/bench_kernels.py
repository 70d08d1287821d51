"""Kernel microbench: jnp reference path wall time per call on this host
(the TPU kernels are validated in interpret mode by tests/; wall numbers
here are the CPU reference path, 'derived' reports achieved GFLOP/s)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run() -> list[dict]:
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 6)
    rows = []

    B, S, Hq, Hkv, D = 2, 512, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    kk = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    fn = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v))
    t = _time(fn, q, kk, v)
    flops = 4 * B * Hq * S * S * D
    rows.append({"name": "kernel_flash_attention_ref", "us_per_call": t * 1e6,
                 "derived": f"gflops={flops / t / 1e9:.2f}"})

    qd = jax.random.normal(ks[3], (B, Hq, D))
    lens = jnp.full((B,), S, jnp.int32)
    fn = jax.jit(lambda q, k, v, l: ops.decode_attention(q, k, v, l))
    t = _time(fn, qd, kk, v, lens)
    rows.append({"name": "kernel_decode_attention_ref", "us_per_call": t * 1e6,
                 "derived": f"cache_tokens_per_s={B * S / t:.0f}"})

    x = jax.random.normal(ks[4], (B, S, 1024))
    sc = jnp.zeros((1024,))
    fn = jax.jit(lambda x, s: ops.rmsnorm(x, s))
    t = _time(fn, x, sc)
    rows.append({"name": "kernel_rmsnorm_ref", "us_per_call": t * 1e6,
                 "derived": f"gbps={x.size * 8 / t / 1e9:.2f}"})

    H, P, N = 4, 32, 64
    xs = jax.random.normal(ks[5], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, H)))
    A = -jnp.linspace(1, 8, H)
    Bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    fn = jax.jit(lambda *a: ops.ssm_scan(*a, chunk=128)[0])
    t = _time(fn, xs, dt, A, Bm, Cm)
    rows.append({"name": "kernel_ssm_scan_ref", "us_per_call": t * 1e6,
                 "derived": f"tokens_per_s={B * S / t:.0f}"})

    from repro.models.ssm import _mlstm_chunked
    q2 = jax.random.normal(ks[0], (B, S, H, 64))
    k2 = jax.random.normal(ks[1], (B, S, H, 64))
    v2 = jax.random.normal(ks[2], (B, S, H, 64))
    li = jax.random.normal(ks[3], (B, S, H)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)
    fn = jax.jit(lambda *a: _mlstm_chunked(*a, 128)[0])
    t = _time(fn, q2, k2, v2, li, lf)
    rows.append({"name": "kernel_mlstm_scan_ref", "us_per_call": t * 1e6,
                 "derived": f"tokens_per_s={B * S / t:.0f}"})
    return rows


if __name__ == "__main__":
    for row in run():
        print(f"{row['name']},{row['us_per_call']:.3f},{row['derived']}")
