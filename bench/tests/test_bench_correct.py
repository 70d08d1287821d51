"""What decides `correct`, at smoke size on the CPU: the fp8 control reads
above the limit where the program reads below it, and a broken timed path
comes out not correct."""
import argparse

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.test_bench_cells import SMOKE_LOGIT_GAP, measure, root  # noqa: F401
from repro.models import lm


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 12])
def test_fp8_control_fails_where_the_program_passes(root, seed):  # noqa: F811
    cell = harness.Cell("h2o.batch_decode", root)
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    counter = harness.CompileCounter()
    try:
        rec = cell.driver.run(cell, args, jax.devices()[:1], 0.0, counter,
                              control=True)
    finally:
        counter.close()
    assert rec["checks"]["logit_gap"]["value"] <= SMOKE_LOGIT_GAP < rec["control_gap"]


def _state_unchanged(real):
    def step(params, cfg, tokens, positions, cache):
        logits, _ = real(params, cfg, tokens, positions, cache)
        return logits, cache
    return step


def _token_altered(real):
    def step(params, cfg, tokens, positions, cache):
        logits, new = real(params, cfg, tokens, positions, cache)
        return jnp.roll(logits, 1, axis=-1), new
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_a_broken_decode_step_is_not_correct(root, monkeypatch, fault):  # noqa: F811
    monkeypatch.setattr(lm, "decode_step", fault(lm.decode_step))
    out = measure(root, "h2o.batch_decode", seed=21)
    assert out["result"]["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
