"""Compile for a described TPU v5e, at real widths, without a chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described and not attached.  What interpret mode cannot show -- block
shapes Mosaic refuses, VMEM overflow, a program larger than HBM -- fails
here.  Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.  Keep these tests in this one file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssm_scan
from repro.models import attention as attn
from repro.models import blocks, lm
from repro.serving import continuous

HBM_BYTES = 15.75e9       # what XLA lets one v5e program use
SEQ = 2048                # kernel sequence length
SLOTS, MAX_LEN, CHUNK = 8, 2048, 256   # chip_smoke.py's batcher


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, cfg):
    """(fn, [(shape, dtype)]) for one kernel at cfg's widths."""
    bf, f32 = jnp.bfloat16, jnp.float32
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if name == "flash":
        window = cfg.sliding_window if "local" in cfg.block_pattern else 0
        return (lambda q, k, v: flash_attention(q, k, v, window=window,
                                                interpret=False),
                [((1, SEQ, hq, hd), bf), ((1, SEQ, hkv, hd), bf),
                 ((1, SEQ, hkv, hd), bf)])
    if name == "decode":
        return (lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
                [((SLOTS, hq, hd), bf), ((SLOTS, MAX_LEN, hkv, hd), bf),
                 ((SLOTS, MAX_LEN, hkv, hd), bf), ((SLOTS,), jnp.int32)])
    if name == "ssm":
        p, n = cfg.d_inner // hq, cfg.ssm_state
        return (lambda x, dt, a, b, c: ssm_scan(x, dt, a, b, c,
                                                chunk=cfg.ssm_chunk,
                                                interpret=False),
                [((1, SEQ, hq, p), bf), ((1, SEQ, hq), bf), ((hq,), f32),
                 ((1, SEQ, n), bf), ((1, SEQ, n), bf)])
    if name == "mlstm":
        d = cfg.d_model // hq
        return (lambda q, k, v, li, lf: mlstm_scan(q, k, v, li, lf,
                                                   chunk=cfg.ssm_chunk,
                                                   interpret=False),
                [((1, SEQ, hq, d), bf)] * 3 + [((1, SEQ, hq), f32)] * 2)
    if name == "rmsnorm":
        return (lambda x, s: rmsnorm(x, s, interpret=False),
                [((SEQ, cfg.d_model), bf), ((cfg.d_model,), bf)])
    raise ValueError(name)


@pytest.mark.parametrize("kernel,arch", [
    ("flash", "h2o_danube_3_4b"), ("flash", "zamba2_1_2b"),
    ("decode", "h2o_danube_3_4b"), ("decode", "zamba2_1_2b"),
    ("ssm", "zamba2_1_2b"), ("mlstm", "xlstm_1_3b"),
    ("rmsnorm", "h2o_danube_3_4b"), ("rmsnorm", "zamba2_1_2b"),
    ("rmsnorm", "xlstm_1_3b"),
])
def test_kernel_compiles_for_v5e(one_chip, kernel, arch):
    fn, shapes = _kernel_case(kernel, registry.get_config(arch))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel} at {arch} widths compiled without its Pallas kernel"


def _bench_config(arch):
    """The two served configurations as the benchmark runs them: h2o with
    global attention in every layer, and stage 1 of zamba2_7b (27 layers,
    invocations at 6, 11, 17, 23); any other registry entry as published;
    all with bf16 weights."""
    if arch == "h2o_danube_3_4b":
        return registry.get_config(arch).replace(
            param_dtype="bfloat16", block_pattern=("global",), sliding_window=0)
    if arch == "zamba2_7b":
        return registry.get_config(arch).replace(
            n_layers=27, hybrid_layer_ids=(6, 11, 17, 23), param_dtype="bfloat16")
    return registry.get_config(arch).replace(param_dtype="bfloat16")


def _serving_args(one_chip, step, cfg):
    """(params, cache, tokens, positions) shapes on one described v5e for
    lm.decode_step (8 slots, one token each) or lm.prefill_chunk (one row,
    CHUNK tokens)."""
    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    params = place(jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                         cfg)))
    rows, width = (SLOTS, 1) if step == "decode_step" else (1, CHUNK)
    cache = place(jax.eval_shape(lambda: lm.init_cache(cfg, rows, MAX_LEN)))
    tok = jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip)
    pos_shape = (SLOTS,) if step == "decode_step" else (1, CHUNK)
    pos = jax.ShapeDtypeStruct(pos_shape, jnp.int32, sharding=one_chip)
    return params, cache, tok, pos


def _compile_serving_step(one_chip, step, cfg):
    """The batcher's decode program (continuous.decode_program: the cache
    donated) or its prefill chunk for cfg, compiled for one described v5e."""
    params, cache, tok, pos = _serving_args(one_chip, step, cfg)
    if step == "decode_step":
        fn = continuous.decode_program(cfg)
    else:
        fn = jax.jit(lambda p, c, t, q: lm.prefill_chunk(p, cfg, t, q, c))
    return fn.lower(params, cache, tok, pos).compile()


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_h2o_serving_step_fits_one_v5e(one_chip, step):
    """The batcher's two programs for h2o-danube-3-4b at published widths,
    bf16 weights, at chip_smoke.py's slots and length."""
    cfg = registry.get_config("h2o_danube_3_4b").replace(param_dtype="bfloat16")
    compiled = _compile_serving_step(one_chip, step, cfg)
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes
    assert used < HBM_BYTES, f"{step}: {used / 1e9:.2f} GB"


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
def test_zamba2_7b_serving_step_fits_one_v5e(one_chip, step):
    """The batcher's two programs for one zamba2_7b pipeline stage (27 layers,
    invocations at 6, 11, 17, 23) at published widths, bf16 weights, 8
    slots x 2048 positions: at most 15 GB, weights, caches and temporaries."""
    compiled = _compile_serving_step(one_chip, step, _bench_config("zamba2_7b"))
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes
    assert used <= 15e9, f"{step}: {used / 1e9:.2f} GB"


def test_h2o_decode_step_contracts_grouped_queries_on_v5e(one_chip):
    """The decode step as the benchmark serves it (global attention in every
    layer, bf16) attends straight from the bf16 cache: no f32 temporary of
    the cache's size spread over all 32 query heads, as a repeat of the 8
    KV heads lowered to (0.29 GB of temporaries), and almost no temps."""
    cfg = _bench_config("h2o_danube_3_4b")
    compiled = _compile_serving_step(one_chip, "decode_step", cfg)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64e6, f"decode step temporaries {temp / 1e9:.3f} GB"
    per_head_cache = SLOTS * MAX_LEN * cfg.n_heads * cfg.head_dim
    wide = []
    for line in compiled.as_text().splitlines():
        result = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\([^=]*?\)|\S+) ", line)
        for dims in re.findall(r"f32\[([\d,]*)\]", result.group(1) if result else ""):
            if math.prod(int(n) for n in dims.split(",") if n) >= per_head_cache:
                wide.append(line.strip()[:160])
    assert not wide, "f32 results of the cache's size per query head:\n" + \
        "\n".join(wide)


def _top_level_results(hlo: str):
    """(op, instruction name, element count) of every instruction outside
    fused computations: each is a buffer the program writes."""
    fused = set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        r = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                     line)
        if r and comp not in fused and r.group(3) not in (
                "parameter", "get-tuple-element", "bitcast", "tuple"):
            n = math.prod(int(d) for d in r.group(2).split(",") if d)
            out.append((r.group(3), r.group(1), n))
    return out


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes


def _stack_copies(hlo: str, cfg):
    """Top-level copies and dynamic-update-slices of at least two layers'
    K/V: a whole stack moved."""
    layer = math.prod(attn.gqa_cache_shape(cfg, SLOTS, MAX_LEN)["k"])
    return [r for r in _top_level_results(hlo) if r[2] >= 2 * layer and
            (r[0] == "copy" or "dynamic-update-slice" in r[1])]


@pytest.mark.parametrize("arch", ["h2o_danube_3_4b", "granite_moe_3b_a800m"])
def test_kv_width_padding_is_what_keeps_the_cache_in_place_on_v5e(
        one_chip, arch, monkeypatch):
    """Head dims 120 and 64 at published widths.  With rows one head dim
    wide, the TPU lays the cache out sequence-minor, and the batcher's
    decode program relayouts whole K/V stacks into and out of its scan,
    with two caches of temporaries.  With rows padded to 128 lanes
    (attention.kv_width) it moves no stack, and its peak is no higher,
    though the padded K/V cache holds 6.7% (120) or 100% (64) more."""
    cfg = _bench_config(arch)
    padded = _compile_serving_step(one_chip, "decode_step", cfg)
    assert not _stack_copies(padded.as_text(), cfg)
    monkeypatch.setattr(attn, "kv_width", lambda c: c.head_dim)
    narrow = _compile_serving_step(one_chip, "decode_step", cfg)
    assert _stack_copies(narrow.as_text(), cfg), \
        "the TPU keeps one-head-dim rows in place: kv_width need not pad"
    assert _peak_bytes(padded) <= _peak_bytes(narrow), \
        f"{_peak_bytes(padded) / 1e9:.3f} GB padded, {_peak_bytes(narrow) / 1e9:.3f} GB not"


@pytest.mark.parametrize("arch", ["h2o_danube_3_4b", "zamba2_7b", "granite_moe_3b_a800m"])
def test_batcher_decode_writes_its_cache_in_place_on_v5e(one_chip, arch):
    """The batcher's decode program at the benchmark's sizes (and, as
    published, granite_moe_3b_a800m: head dim 64, a mixture of experts)
    donates every cache leaf to its output, keeps temporaries small, and
    touches each K/V leaf's layer with at most two layer-sized ops (its
    slice, and the attention's relayout of it): no layer is relayouted
    back, no stack is restacked by a dynamic-update-slice, and no stack or
    cache is copied."""
    cfg = _bench_config(arch)
    params, cache, _, _ = _serving_args(one_chip, "decode_step", cfg)
    compiled = _compile_serving_step(one_chip, "decode_step", cfg)
    hlo = compiled.as_text()
    n_params, n_cache = (len(jax.tree_util.tree_leaves(t)) for t in (params, cache))
    aliased = {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}",
                                          hlo.split("entry_computation_layout")[0])}
    assert aliased == set(range(n_params, n_params + n_cache)), \
        f"cache parameters aliased to outputs: {sorted(aliased)}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.25e9, f"decode step temporaries {temp / 1e9:.3f} GB"

    layer = math.prod(attn.gqa_cache_shape(cfg, SLOTS, MAX_LEN)["k"])
    # each phase's scan body is compiled once, whatever its group count
    sites = sum(2 * (sum(k in ("global", "local") for k in ph.kinds) + ph.hybrid)
                for ph in blocks.build_plan(cfg))
    results = _top_level_results(hlo)
    per_layer = [r for r in results if r[2] == layer]
    assert len(per_layer) <= 2 * sites, \
        f"{len(per_layer)} layer-sized ops for {sites} K/V leaves: {per_layer}"
    whole = _stack_copies(hlo, cfg)
    assert not whole, f"whole-stack copies or restacks: {whole}"
