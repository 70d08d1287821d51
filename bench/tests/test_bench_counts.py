"""Counts of work from shapes, tied to the program's own parameter tree,
and the peaks table."""
import jax
import pytest

from bench import harness
from bench.tests import smoke
from repro.models import lm

H2O = harness.load_json(smoke.BENCH / "configs" / "h2o_danube_3_4b.json")
MODEL = harness.load_module(smoke.BENCH / "configs" / "h2o_danube_3_4b.py")


def _program_params(conf):
    cfg = harness.program_config(conf)
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes)), shapes


@pytest.mark.parametrize("size", ["published", "smoke"])
def test_h2o_param_count_matches_the_program(size):
    conf = dict(H2O)
    if size == "smoke":
        conf.update(smoke.H2O_SMOKE)
        conf["program"] = dict(conf["program"], replace={
            **conf["program"]["replace"], **smoke.H2O_PROGRAM})
    n, shapes = _program_params(conf)
    assert MODEL.param_count(conf) == n
    # the published config has no window: every layer attends globally
    assert set(harness.program_config(conf).block_pattern) == {"global"}
    if size == "published":
        assert n == 3_961_839_360          # 3.962 B
    # every leaf of the program's tree is one the weight spec knows
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names <= set(MODEL.WEIGHTS)


def test_h2o_flops_and_least_decode_bytes_from_shapes():
    mm = MODEL.matmul_params(H2O)
    assert mm == MODEL.param_count(H2O) - 32000 * 3840 - 49 * 3840
    # attention over 100 positions: 4 * layers * heads * head_dim * 100
    assert MODEL.flops_per_token(H2O, 99) == 2 * mm + 4 * 24 * 32 * 120 * 100
    row = 24 * 2 * 8 * 120 * 2                 # K and V of one position
    assert MODEL.decode_min_bytes(H2O, [10, 20]) == \
        2 * (mm + 2 * 3840) + (11 + 21) * row


def test_peaks_unknown_device_kind_raises():
    table = smoke.BENCH / "peaks.json"
    assert harness.peaks("TPU v5 lite", table)["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks("cpu", table)


def test_compile_counter_counts_only_while_active():
    import jax.numpy as jnp
    counter = harness.CompileCounter()
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7))
        assert counter.count == 0
        counter.active = True
        jax.jit(lambda x: x * 5 - 2)(jnp.ones(9))
        assert counter.count >= 1
    finally:
        counter.close()
