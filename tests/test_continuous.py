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
    r = cb.submit([1, 2, 3], max_new=4)          # needs 7 steps total
    assert cb.run(max_steps=4) == []             # budget exhausted mid-flight
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


@pytest.mark.parametrize("prefill_chunk", [0, 4])
def test_requests_carry_their_clock_times(setup, prefill_chunk):
    """t_submit <= t_admit <= t_first <= t_done on both admission paths;
    under backlog the last request waits in the queue longest."""
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
