"""Continuous-batching decode engine: slot reuse, admission, isolation,
and agreement with the plain batched decode path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import lm, steps
from repro.serving.continuous import ContinuousBatcher


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("h2o_danube_3_4b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reference_generate(cfg, params, prompt, n_new, cache_len=64):
    """Plain prefill + greedy loop on a batch of one."""
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    last, cache = steps.prefill(params, batch, cfg=cfg, cache_len=cache_len)
    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    start = jnp.full((1,), len(prompt), jnp.int32)
    toks, _ = steps.greedy_decode_loop(params, cache, tok, start, n_new - 1,
                                       cfg=cfg)
    return [int(tok[0, 0])] + [int(t) for t in np.asarray(toks)[0]]


def test_single_request_matches_reference(setup):
    cfg, params = setup
    prompt = [5, 17, 99, 3]
    want = _reference_generate(cfg, params, prompt, 6)
    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=64)
    req = cb.submit(prompt, max_new=6)
    cb.run()
    assert req.done
    assert req.output == want, (req.output, want)


def test_concurrent_requests_are_isolated(setup):
    """Each request's output must equal its solo run (no cross-slot leaks)."""
    cfg, params = setup
    prompts = [[5, 17, 99, 3], [200, 41], [7, 7, 7, 7, 7, 7]]
    solo = [_reference_generate(cfg, params, p, 5) for p in prompts]
    cb = ContinuousBatcher(cfg, params, max_slots=3, max_len=64)
    reqs = [cb.submit(p, max_new=5) for p in prompts]
    cb.run()
    for r, want in zip(reqs, solo):
        assert r.done and r.output == want


def test_slot_reuse_more_requests_than_slots(setup):
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=64)
    reqs = [cb.submit([i + 1, i + 2], max_new=3) for i in range(5)]
    done = cb.run()
    assert len(done) == 5
    assert all(len(r.output) == 3 for r in reqs)
    # later requests were admitted after earlier ones finished
    assert max(r.admitted_step for r in reqs) > 0


def test_slot_reuse_output_independent_of_previous_occupant(setup):
    """A prompt served after slot reuse equals its solo generation."""
    cfg, params = setup
    target = [42, 43, 44]
    want = _reference_generate(cfg, params, target, 4)
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
    cb.submit([9, 8, 7, 6, 5], max_new=4)   # previous occupant
    tgt = cb.submit(target, max_new=4)
    cb.run()
    assert tgt.output == want


def test_ssm_state_reset_on_admission():
    """Recurrent-state arch: slot reuse must not inherit the carry."""
    cfg = registry.get_smoke_config("zamba2_1_2b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    target = [11, 12, 13]
    want = _reference_generate(cfg, params, target, 3)
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
    cb.submit([400, 300, 200, 100], max_new=3)
    tgt = cb.submit(target, max_new=3)
    cb.run()
    assert tgt.output == want


def test_submit_rids_stay_unique_after_admission(setup):
    """Regression: rid=len(queue) reused rids once admission popped the
    queue, corrupting run()'s seen-set; rids must be monotonic."""
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
    r1 = cb.submit([1, 2], max_new=2)
    cb.step()                        # admits r1 -> queue drains to empty
    r2 = cb.submit([3, 4], max_new=2)
    assert r1.rid != r2.rid
    done = cb.run()
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert r1.done and r2.done


def test_run_returns_each_request_exactly_once(setup):
    """Repeated submit/run cycles: a request finished and returned by one
    run() must not be returned again by the next (and stops being
    tracked, so long-lived batchers don't accumulate requests)."""
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
    r1 = cb.submit([1, 2], max_new=2)
    assert [r.rid for r in cb.run()] == [r1.rid]
    r2 = cb.submit([3, 4], max_new=2)
    assert [r.rid for r in cb.run()] == [r2.rid]
    assert cb.requests == []


def test_max_steps_bounds_each_run_call(setup):
    """max_steps is a per-call budget: a long-lived batcher must keep
    draining on later run() calls, not die at a lifetime step cap."""
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
    # the first token comes at admission, the other 7 one a step
    r = cb.submit([1, 2, 3], max_new=8)
    assert cb.run(max_steps=4) == []             # budget exhausted mid-flight
    assert len(r.output) == 5
    done = cb.run(max_steps=50)                  # fresh budget resumes
    assert [x.rid for x in done] == [r.rid] and r.done


def test_eos_frees_slot_early(setup):
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64, eos_id=None)
    r = cb.submit([1, 2, 3], max_new=50)
    # force EOS on the first generated token
    cb.eos_id = None
    cb.run(max_steps=100)
    assert r.done and len(r.output) <= 50


def test_admission_is_fifo_under_backlog(setup):
    """Submission order IS admission order: the queue is a deque popped
    from the head (the old list.pop(0) was quadratic under backlog, and
    any reordering here would starve early requests -- ISSUE 7)."""
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=64)
    reqs = [cb.submit([i + 1, i + 2], max_new=2) for i in range(8)]
    done = cb.run()
    assert len(done) == 8
    admits = [r.admitted_step for r in reqs]     # indexed by rid order
    assert admits == sorted(admits)              # FIFO: never leapfrogged
    assert all(r.admitted_step >= 0 and r.finished_step >= r.admitted_step
               for r in reqs)


@pytest.mark.parametrize("prefill_chunk", [1, 4])
def test_requests_carry_their_clock_times(setup, prefill_chunk):
    """t_submit <= t_admit <= t_first <= t_done, whether the prompt takes
    one prefill call or several; under backlog the last request waits in
    the queue longest."""
    cfg, params = setup
    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=64,
                           prefill_chunk=prefill_chunk)
    reqs = [cb.submit([i + 1, i + 2, i + 3], max_new=3) for i in range(5)]
    assert all(r.t_admit is None and r.t_first is None for r in reqs)
    cb.run()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    waits = [r.t_admit - r.t_submit for r in reqs]
    assert waits[-1] == max(waits)


@pytest.mark.parametrize("bad", ["empty_prompt", "prefill_chunk_0"])
def test_inputs_that_cannot_be_admitted_are_refused(setup, bad):
    """An empty prompt has nothing to prefill (it used to raise IndexError
    at the first step), and a prompt is admitted only by chunked prefill,
    so a chunk below one token is refused when the batcher is built."""
    cfg, params = setup
    if bad == "prefill_chunk_0":
        with pytest.raises(ValueError, match="prefill_chunk"):
            ContinuousBatcher(cfg, params, max_slots=1, max_len=64,
                              prefill_chunk=0)
    else:
        cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64)
        with pytest.raises(ValueError, match="empty prompt"):
            cb.submit([], max_new=2)
        assert not cb.queue and not cb.requests


def _scan_ys_decode_step(params, cfg, tokens, positions, cache):
    """Reference: the decode step before its cache rode in the layer scan's
    carry.  Each phase's cache is scanned as xs and restacked as ys, one
    layer at a time, and nothing is donated; the layer code is handed each
    layer's cache as a stack of one and writes it at index 0."""
    from repro.models import attention as attn
    from repro.models import blocks
    from repro.models import modules as nn
    params = nn.cast_tree(params, cfg.compute_dtype)
    x = emb = lm._embed(params, cfg, tokens)
    new_cache = {}
    for pi, phase in enumerate(blocks.build_plan(cfg)):
        def group_fn(h, xs, phase=phase):
            gp, gc, g = xs
            one = jax.tree_util.tree_map(lambda a: a[None], gc)
            out, shared = {}, None
            if phase.hybrid:
                w = lm._cached_window(cfg, gc["shared"])
                shared, out["shared"] = blocks.shared_block(
                    lm._shared_params(params, cfg, phase, g), gp["hybrid"], h, emb,
                    cfg, lambda mp, s: attn.gqa_decode(mp, s, one["shared"], 0,
                                                       positions, cfg, window=w))
            for j, (kind, ffn) in enumerate(zip(phase.kinds, phase.ffns)):
                h, out[f"slot{j}"] = blocks.slot_decode(
                    gp[f"slot{j}"], h, one[f"slot{j}"], 0, positions, cfg, kind,
                    ffn, shared=shared if j == 0 else None)
            return h, jax.tree_util.tree_map(lambda a: a[0], out)

        x, new_cache[f"phase{pi}"] = jax.lax.scan(
            group_fn, x, (params[f"phase{pi}"], cache[f"phase{pi}"],
                          jnp.arange(phase.n_groups)))
    return lm._head(params, cfg, x[:, 0]), new_cache


# one smoke config per kind of cache, with two groups in a phase where the
# smoke config has one, so that the step writes at a layer index past 0
CACHE_KINDS = {
    "h2o_danube_3_4b": dict(block_pattern=("global",), sliding_window=0),
    "gemma3_4b": dict(n_layers=4),                  # ring (window 16) + global
    "deepseek_v2_lite_16b": dict(n_layers=3),       # MLA after a dense layer
    "zamba2_7b": dict(n_layers=6, hybrid_layer_ids=(1, 3, 5)),  # Mamba2 + shared K/V
    "xlstm_1_3b": dict(n_layers=4),                 # mLSTM / sLSTM state
}


def _serve(cb, prompts):
    """Two requests, three steps, then two more admitted mid-run into slots
    the first ones free.  Returns (outputs, logits of every decode step,
    whether every step's cache was donated)."""
    logits, donated = [], []
    decode = cb._decode

    def recording(p, c, t, pos):
        out = decode(p, c, t, pos)
        logits.append(np.asarray(out[0], np.float32))
        donated.append(all(a.is_deleted() for a in jax.tree_util.tree_leaves(c)))
        return out

    cb._decode = recording
    reqs = [cb.submit(p, max_new=n) for p, n in prompts[:2]]
    for _ in range(3):
        cb.step()
    reqs += [cb.submit(p, max_new=n) for p, n in prompts[2:]]
    cb.run()
    return [r.output for r in reqs], np.stack(logits), all(donated)


@pytest.mark.parametrize("arch", sorted(CACHE_KINDS))
def test_donated_decode_matches_scan_ys_reference(arch):
    """The batcher's decode step, with the cache carried, donated and held
    in one layout, serves the tokens and logits of the undonated scan-ys
    form, through admissions that reset and scatter rows of a donated
    cache, past the ring window."""
    cfg = registry.get_smoke_config(arch).replace(**CACHE_KINDS[arch])
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [(rng.integers(1, cfg.vocab_size, n).tolist(), m)
               for n, m in ((8, 3), (16, 12), (24, 9), (8, 6))]

    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=40, prefill_chunk=8)
    got, got_logits, donated = _serve(cb, prompts)
    assert donated, "a decode step kept the cache it was given"

    ref = ContinuousBatcher(cfg, params, max_slots=2, max_len=40, prefill_chunk=8)
    ref._decode = jax.jit(
        lambda p, c, t, pos: _scan_ys_decode_step(p, cfg, t, pos, c))
    want, want_logits, _ = _serve(ref, prompts)

    assert got == want
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-3, atol=2e-3)
