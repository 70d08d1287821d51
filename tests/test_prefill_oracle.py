"""Oracle suite for the chunked batched prefill path (ISSUE 8 tentpole).

`lm.prefill_chunk` replaces the 1-token-per-step teacher-forced prompt
catch-up in ContinuousBatcher: C prompt tokens per call, KV cache rows
written directly, decode-exact masking.  These tests pin it to the
teacher-forced `lm.decode_step` reference:

  * BITWISE archs: logits at every prompt position AND the final cache are
    bit-identical to running decode_step once per token.  This holds for
    every single-phase program (pure global / M-RoPE / ring-window local /
    MLA) on the XLA CPU backend.
  * TOKENWISE archs (gemma3 local+global mix, xlstm mlstm+slstm mix,
    deepseek dense-first+moe two-phase): XLA CPU specializes transcendental
    codegen per program context, so multi-phase programs drift by ~1 ulp
    between the chunked and per-token compilations.  The zamba2 hybrids
    are multi-phase too, and their Mamba2 chunk prefill runs the chunked
    SSD form, another order of the decode recurrence's sums.  For those the
    oracle asserts argmax equality at every position plus a tight allclose.

The batcher-level property (hypothesis + seeded fallback, rotating-seed CI
pass) asserts the prefill-enabled ContinuousBatcher emits exactly the same
output tokens as the teacher-forced seed batcher across random prompt
mixes, chunk sizes and slot counts -- plus the unbounded-prompt regression
(a prompt with len >= max_len used to walk `pos` past the cache bound with
its KV scatter silently dropped; submit() now rejects it).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import lm
from repro.serving.continuous import ContinuousBatcher

try:
    from hypothesis import given, strategies as hyp_st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

# empirically bit-stable single-phase programs (see module docstring)
BITWISE_ARCHS = ("h2o_danube_3_4b", "qwen2_vl_7b", "minitron_4b",
                 "granite_3_8b", "granite_moe_3b_a800m")
# multi-phase programs: ~1-ulp context-sensitive codegen, argmax stable;
# the hybrids' chunked SSD prefill: rounding-level differences
TOKENWISE_ARCHS = ("gemma3_4b", "xlstm_1_3b", "deepseek_v2_lite_16b",
                   "zamba2_1_2b", "zamba2_7b")


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = registry.get_smoke_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _positions(cfg, t0, c):
    pos = jnp.arange(t0, t0 + c, dtype=jnp.int32)[None]
    if cfg.use_mrope:
        pos = jnp.broadcast_to(pos[:, None], (1, 3, c))
    return pos


def _teacher_forced(cfg, params, toks, cache_len):
    """Reference: one decode_step per prompt token at B=1."""
    dec = jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, t, pos, c))
    cache = lm.init_cache(cfg, 1, cache_len)
    logits = []
    for t in range(toks.shape[1]):
        pos = jnp.array([t], jnp.int32)
        if cfg.use_mrope:
            pos = jnp.broadcast_to(pos[:, None], (1, 3))
        lg, cache = dec(params, cache, toks[:, t:t + 1], pos)
        logits.append(lg)
    return jnp.stack(logits, axis=1), cache


def _chunked(cfg, params, toks, cache_len, chunk):
    pf = jax.jit(lambda p, c, t, pos: lm.prefill_chunk(p, cfg, t, pos, c))
    cache = lm.init_cache(cfg, 1, cache_len)
    outs, t0, n = [], 0, toks.shape[1]
    while t0 < n:
        c = min(chunk, n - t0)
        lg, cache = pf(params, cache, toks[:, t0:t0 + c], _positions(cfg, t0, c))
        outs.append(lg)
        t0 += c
    return jnp.concatenate(outs, axis=1), cache


def _cache_leaves(cache):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(cache)}


@pytest.mark.parametrize("arch", BITWISE_ARCHS)
def test_prefill_bitwise_oracle(arch):
    cfg, params = _setup(arch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
    ref_logits, ref_cache = _teacher_forced(cfg, params, toks, 64)
    pf_logits, pf_cache = _chunked(cfg, params, toks, 64, chunk=5)
    assert bool(jnp.all(pf_logits == ref_logits)), (
        f"{arch}: prefill logits not bit-identical to teacher-forced decode "
        f"(max |diff| {float(jnp.max(jnp.abs(pf_logits - ref_logits))):.3g})")
    ref_leaves, pf_leaves = _cache_leaves(ref_cache), _cache_leaves(pf_cache)
    assert ref_leaves.keys() == pf_leaves.keys()
    for k in ref_leaves:
        assert bool(jnp.all(ref_leaves[k] == pf_leaves[k])), (
            f"{arch}: cache leaf {k} not bit-identical")


@pytest.mark.parametrize("arch", TOKENWISE_ARCHS)
def test_prefill_tokenwise_oracle(arch):
    cfg, params = _setup(arch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
    ref_logits, ref_cache = _teacher_forced(cfg, params, toks, 64)
    pf_logits, pf_cache = _chunked(cfg, params, toks, 64, chunk=5)
    assert bool(jnp.all(jnp.argmax(pf_logits, -1) == jnp.argmax(ref_logits, -1)))
    np.testing.assert_allclose(np.asarray(pf_logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4)
    ref_leaves, pf_leaves = _cache_leaves(ref_cache), _cache_leaves(pf_cache)
    for k in ref_leaves:
        np.testing.assert_allclose(
            np.asarray(ref_leaves[k], np.float32),
            np.asarray(pf_leaves[k], np.float32), rtol=1e-3, atol=1e-4,
            err_msg=f"{arch}: cache leaf {k}")


def test_prefill_chunk_size_invariant():
    """Chunk size must not change logits at all (same program family)."""
    cfg, params = _setup("h2o_danube_3_4b")
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, cfg.vocab_size)
    base, _ = _chunked(cfg, params, toks, 64, chunk=16)
    for chunk in (1, 3, 8):
        lg, _ = _chunked(cfg, params, toks, 64, chunk=chunk)
        assert bool(jnp.all(lg == base)), f"chunk={chunk} changed logits"


# -- batcher-level property --------------------------------------------------

def _batcher_outputs(arch, prompts, max_new, max_slots, chunk):
    cfg, params = _setup(arch)
    out = {}
    for pc in (0, chunk):
        b = ContinuousBatcher(cfg, params, max_slots=max_slots, max_len=64,
                              prefill_chunk=pc)
        for p in prompts:
            b.submit(list(p), max_new)
        done = b.run()
        out[pc] = sorted((r.rid, tuple(r.output)) for r in done)
    return out


def _check_scenario(rng):
    arch = ("h2o_danube_3_4b", "gemma3_4b")[int(rng.integers(0, 2))]
    cfg, _ = _setup(arch)
    n_req = int(rng.integers(1, 5))
    prompts = [list(rng.integers(0, cfg.vocab_size, int(rng.integers(1, 14))))
               for _ in range(n_req)]
    max_new = int(rng.integers(1, 6))
    max_slots = int(rng.integers(1, 4))
    chunk = int(rng.integers(1, 8))
    out = _batcher_outputs(arch, prompts, max_new, max_slots, chunk)
    assert out[0] == out[chunk], (
        f"{arch}: prefill batcher diverged from teacher-forced seed "
        f"(slots={max_slots}, chunk={chunk}, prompts={prompts})")


if HAS_HYPOTHESIS:
    @given(hyp_st.integers(min_value=0, max_value=2**32 - 1))
    def test_batcher_prefill_equals_teacher_forced(seed):
        _check_scenario(np.random.default_rng(seed))


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_batcher_prefill_equals_teacher_forced_seeded(seed):
    _check_scenario(np.random.default_rng(seed))


# -- unbounded-prompt regression (ISSUE 8 satellite) -------------------------

@pytest.mark.parametrize("chunk", [0, 4])
def test_unbounded_prompt_rejected(chunk):
    """Before the fix a prompt with len >= max_len was admitted, its pos
    walked past the cache bound (KV scatter silently dropped out-of-range
    rows) and the request terminated with garbage; submit() now rejects."""
    cfg, params = _setup("h2o_danube_3_4b")
    b = ContinuousBatcher(cfg, params, max_slots=1, max_len=16,
                          prefill_chunk=chunk)
    with pytest.raises(ValueError, match="max_len"):
        b.submit(list(range(1, 17)), max_new=4)
    with pytest.raises(ValueError, match="max_len"):
        b.submit(list(range(1, 40)), max_new=4)
    # the longest admissible prompt still produces output
    req = b.submit(list(range(1, 16)), max_new=4)
    done = b.run()
    assert [r.rid for r in done] == [req.rid] and len(req.output) >= 1
