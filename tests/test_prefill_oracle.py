"""Oracle suite for the chunked batched prefill path (ISSUE 8 tentpole).

`lm.prefill_chunk` is how ContinuousBatcher admits a prompt: C prompt
tokens per call, KV cache rows written directly, decode-exact masking.
These tests hold it to the teacher-forced `lm.decode_step` reference, one
decode step per prompt token at B=1:

  * every arch: argmax equality of the logits at every prompt position,
    the logits within rtol/atol 1e-4 and every final cache leaf within
    rtol 1e-3 / atol 1e-4.  A tolerance and not bit equality: XLA CPU
    specializes codegen per program, so the chunked and per-token
    compilations differ by a few ulp (max |diff| ~3e-6 on the single-phase
    archs since JAX 0.9, ~1 ulp on the multi-phase ones), and the zamba2
    hybrids' Mamba2 chunk prefill runs the chunked SSD form, another order
    of the decode recurrence's sums.
  * chunk size: chunks 1, 3 and 8 give the 16-token chunk's logits within
    the same tolerance and argmax.

The batcher-level property (hypothesis + seeded fallback, rotating-seed CI
pass) asserts every request served by ContinuousBatcher emits exactly its
prompt's solo greedy generation through the reference, across random
prompt mixes, chunk sizes and slot counts -- plus the unbounded-prompt
regression (a prompt with len >= max_len used to walk `pos` past the cache
bound with its KV scatter silently dropped; submit() now rejects it).
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

ARCHS = ("h2o_danube_3_4b", "qwen2_vl_7b", "minitron_4b", "granite_3_8b",
         "granite_moe_3b_a800m", "gemma3_4b", "xlstm_1_3b",
         "deepseek_v2_lite_16b", "zamba2_1_2b", "zamba2_7b")


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


@functools.lru_cache(maxsize=None)
def _decode_fn(arch):
    cfg, _ = _setup(arch)
    return jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, t, pos, c))


def _decode_pos(cfg, t):
    pos = jnp.array([t], jnp.int32)
    if cfg.use_mrope:
        pos = jnp.broadcast_to(pos[:, None], (1, 3))
    return pos


def _teacher_forced(arch, toks, cache_len):
    """Reference: one decode_step per prompt token at B=1."""
    cfg, params = _setup(arch)
    dec = _decode_fn(arch)
    cache = lm.init_cache(cfg, 1, cache_len)
    logits = []
    for t in range(toks.shape[1]):
        lg, cache = dec(params, cache, toks[:, t:t + 1], _decode_pos(cfg, t))
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


def _assert_logits_close(got, want, what):
    np.testing.assert_array_equal(np.argmax(np.asarray(got), -1),
                                  np.argmax(np.asarray(want), -1),
                                  err_msg=f"{what}: argmax differs")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4, err_msg=what)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_tokenwise_oracle(arch):
    cfg, params = _setup(arch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
    ref_logits, ref_cache = _teacher_forced(arch, toks, 64)
    pf_logits, pf_cache = _chunked(cfg, params, toks, 64, chunk=5)
    _assert_logits_close(pf_logits, ref_logits, arch)
    ref_leaves, pf_leaves = _cache_leaves(ref_cache), _cache_leaves(pf_cache)
    assert ref_leaves.keys() == pf_leaves.keys()
    for k in ref_leaves:
        np.testing.assert_allclose(
            np.asarray(ref_leaves[k], np.float32),
            np.asarray(pf_leaves[k], np.float32), rtol=1e-3, atol=1e-4,
            err_msg=f"{arch}: cache leaf {k}")


def test_prefill_chunk_size_invariant():
    """Chunk size must not change the logits beyond rounding."""
    cfg, params = _setup("h2o_danube_3_4b")
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, cfg.vocab_size)
    base, _ = _chunked(cfg, params, toks, 64, chunk=16)
    for chunk in (1, 3, 8):
        lg, _ = _chunked(cfg, params, toks, 64, chunk=chunk)
        _assert_logits_close(lg, base, f"chunk={chunk}")


# -- batcher-level property --------------------------------------------------

def _solo_greedy(arch, prompt, max_new, cache_len):
    """The prompt alone through the reference: teacher-forced, then greedy
    decode_steps at B=1."""
    cfg, params = _setup(arch)
    logits, cache = _teacher_forced(
        arch, jnp.asarray([prompt], jnp.int32), cache_len)
    out = [int(jnp.argmax(logits[0, -1]))]
    dec = _decode_fn(arch)
    while len(out) < max_new:
        pos = _decode_pos(cfg, len(prompt) + len(out) - 1)
        lg, cache = dec(params, cache, jnp.asarray([[out[-1]]], jnp.int32), pos)
        out.append(int(jnp.argmax(lg[0])))
    return out


def _check_scenario(rng):
    arch = ("h2o_danube_3_4b", "gemma3_4b")[int(rng.integers(0, 2))]
    cfg, params = _setup(arch)
    n_req = int(rng.integers(1, 5))
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(1, 14))).tolist()
               for _ in range(n_req)]
    max_new = int(rng.integers(1, 6))
    max_slots = int(rng.integers(1, 4))
    chunk = int(rng.integers(1, 8))
    b = ContinuousBatcher(cfg, params, max_slots=max_slots, max_len=64,
                          prefill_chunk=chunk)
    reqs = [b.submit(p, max_new) for p in prompts]
    b.run()
    for p, r in zip(prompts, reqs):
        assert r.output == _solo_greedy(arch, p, max_new, 64), (
            f"{arch}: batcher diverged from the prompt's solo greedy run "
            f"(slots={max_slots}, chunk={chunk}, prompts={prompts})")


if HAS_HYPOTHESIS:
    @given(hyp_st.integers(min_value=0, max_value=2**32 - 1))
    def test_batcher_prefill_equals_teacher_forced(seed):
        _check_scenario(np.random.default_rng(seed))


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_batcher_prefill_equals_teacher_forced_seeded(seed):
    _check_scenario(np.random.default_rng(seed))


# -- unbounded-prompt regression (ISSUE 8 satellite) -------------------------

@pytest.mark.parametrize("chunk", [1, 4])
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
