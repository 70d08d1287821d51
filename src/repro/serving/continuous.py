"""Continuous-batching decode engine (beyond-paper serving feature).

KServe's request-level batching (kserve.py) wastes decode slots when
sequences finish at different times.  This engine keeps a fixed-width slot
pool over ONE shared KV cache and admits queued prompts into freed slots
between steps -- the vLLM-style scheduling pattern, built on the same
models.lm decode path used by the dry-run (per-sequence positions).

Mechanics: a newly admitted prompt runs through the batched prefill path
(lm.prefill_chunk -> the flash-attention style masked chunk attention),
``prefill_chunk`` tokens a call, on a standalone one-row cache; its rows are
written into the slot's cache row, and the sequence enters the decode pool
with its first generated token already emitted.  Every step then advances
ALL slots by one token through lm.decode_step, each busy slot fed its last
generated token.  The oracle suite (tests/test_prefill_oracle.py) holds the
prefill to the teacher-forced decode_step reference.  Idle slots process a
pad token whose writes land in their own cache rows, never leaking across
slots (cache rows are per-sequence).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ArchConfig
from ..models import lm

PAD = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list          # token ids
    max_new: int
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    admitted_step: int = -1
    finished_step: int = -1
    # time.perf_counter() at submit, admission, first token and completion
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


def _emit(req: Request, token: int):
    req.output.append(token)
    if len(req.output) == 1:
        req.t_first = time.perf_counter()


def _on_slot_rows(cache, f, *rows):
    """f(leaf, *row leaves) on every phase-cache leaf, each (G, slots, ...)
    by lm.init_cache's contract; every other leaf as it is."""
    return {k: (jax.tree_util.tree_map(f, v, *(r[k] for r in rows))
                if k.startswith("phase") else v)
            for k, v in cache.items()}


def decode_program(cfg: ArchConfig):
    """lm.decode_step as the batcher serves it: jitted with the cache
    donated, so that its rows are written in place."""
    def serve_decode(p, c, t, pos):
        return lm.decode_step(p, cfg, t, pos, c)

    return jax.jit(serve_decode, donate_argnums=1)


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0              # next cache position for this row


class ContinuousBatcher:
    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_len: int = 128, eos_id: Optional[int] = None,
                 prefill_chunk: int = 256):
        assert cfg.family not in ("audio",), "enc-dec admission not supported"
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk {prefill_chunk} < 1: a prompt is "
                             "admitted by chunked prefill")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.prefill_chunk = int(prefill_chunk)
        self.cache = lm.init_cache(cfg, max_slots, max_len)
        self.slots = [_Slot() for _ in range(max_slots)]
        # deque: admission pops the head every step -- a plain list's
        # pop(0) is O(n) and went quadratic under backlog (ISSUE 7)
        self.queue: collections.deque[Request] = collections.deque()
        self.requests: list[Request] = []   # submitted, not yet run()-returned
        self.step_count = 0
        self._next_rid = 0

        def serve_prefill(p, c, t, pos):
            return lm.prefill_chunk(p, cfg, t, pos, c)

        self._decode = decode_program(cfg)

        def zero_row(c, i):
            return _on_slot_rows(c, lambda a: a.at[:, i].set(0))

        def put_row(c, row, i):
            return _on_slot_rows(
                c, lambda a, r: a.at[:, i].set(r[:, 0].astype(a.dtype)), row)

        # admission writes one slot's row in place, as the decode step does
        self._zero_row = jax.jit(zero_row, donate_argnums=0)
        self._put_row = jax.jit(put_row, donate_argnums=0)
        self._prefill = jax.jit(serve_prefill)
        self._row_cache_zeros = lm.init_cache(cfg, 1, max_len)
        # per-phase counters the two-phase cost model reads
        self.prefill_stats = {"requests": 0, "chunks": 0, "tokens": 0}

    # -- client API ---------------------------------------------------------
    def submit(self, prompt: list, max_new: int) -> Request:
        if not prompt:
            raise ValueError("empty prompt: nothing to prefill")
        # A prompt must leave room for at least one generated token: an
        # unbounded prompt used to walk pos past the cache bound with its KV
        # writes silently dropped (out-of-range scatter) -- reject here.
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_len {self.max_len}: "
                "no room in the KV cache to generate")
        # rid must be monotonic, not len(queue): admission pops the queue, so
        # a later submit would reuse a live rid and corrupt run()'s seen-set.
        req = Request(rid=self._next_rid, prompt=list(prompt), max_new=max_new,
                      t_submit=time.perf_counter())
        self._next_rid += 1
        self.queue.append(req)
        self.requests.append(req)
        return req

    @property
    def active(self) -> int:
        return sum(s.req is not None for s in self.slots)

    def _reset_row(self, i: int):
        """Zero cache row i: KV rows would be masked by eff_len anyway, but
        recurrent state (SSM/mLSTM carries) PERSISTS across occupants and
        must be cleared at re-admission."""
        with TraceAnnotation("serve.reset_row"):
            self.cache = self._zero_row(self.cache, i)

    def _admit(self):
        for i, s in enumerate(self.slots):
            # loop: a prefilled request can finish instantly (max_new=1 /
            # eos / cache bound), freeing the slot for the next in queue
            while s.req is None and self.queue:
                req = self.queue.popleft()
                with TraceAnnotation("serve.admit", rid=req.rid):
                    req.admitted_step = self.step_count
                    req.t_admit = time.perf_counter()
                    s.req = req
                    self._reset_row(i)
                    _emit(req, self._prefill_into(i, req))
                    s.pos = len(req.prompt)
                    self._maybe_finish(s)

    def _maybe_finish(self, s: _Slot):
        """Free the slot if its request is complete: max_new tokens, eos,
        or the end of the cache row."""
        req = s.req
        hit_eos = self.eos_id is not None and req.output[-1] == self.eos_id
        if len(req.output) >= req.max_new or hit_eos or s.pos >= self.max_len - 1:
            req.done = True
            req.finished_step = self.step_count
            req.t_done = time.perf_counter()
            s.req = None               # free the slot for admission

    def _prefill_into(self, i: int, req: Request) -> int:
        """Prefill the prompt, then scatter the produced cache rows into
        slot i.  Returns the first generated token (argmax of the last
        prompt position's logits)."""
        logits, cache = self.prefill(req.prompt, rid=req.rid)
        self._scatter_row(i, cache)
        with TraceAnnotation("serve.admit.sync"):
            return int(np.asarray(jnp.argmax(logits)))

    def prefill(self, prompt: list, rid: int = -1):
        """Run a prompt through lm.prefill_chunk on a zeroed one-row cache,
        `prefill_chunk` tokens per call.  Returns (logits of the last prompt
        position (V,), the one-row cache).  `rid` only labels the trace
        span (-1: no request)."""
        prompt = np.asarray(prompt, np.int32)
        n = len(prompt)
        cache = self._row_cache_zeros
        t0 = 0
        logits = None
        with TraceAnnotation("serve.prefill", rid=rid, tokens=n):
            while t0 < n:
                c = min(self.prefill_chunk, n - t0)
                tok = jnp.asarray(prompt[t0:t0 + c], jnp.int32)[None]
                pos = jnp.arange(t0, t0 + c, dtype=jnp.int32)[None]
                if self.cfg.use_mrope:
                    pos = jnp.broadcast_to(pos[:, None], (1, 3, c))
                logits, cache = self._prefill(self.params, cache, tok, pos)
                self.prefill_stats["chunks"] += 1
                t0 += c
        self.prefill_stats["tokens"] += n
        self.prefill_stats["requests"] += 1
        return logits[0, -1], cache

    def _scatter_row(self, i: int, row_cache):
        """Copy a one-row prefill cache into row i of the shared cache."""
        with TraceAnnotation("serve.scatter_row"):
            self.cache = self._put_row(self.cache, row_cache, i)

    # -- engine -------------------------------------------------------------
    def step(self):
        """Advance every slot one token; admit queued work into free slots."""
        with TraceAnnotation("serve.step"):
            self._admit()
            with TraceAnnotation("serve.step.inputs"):
                tok, pos = self._inputs()
            with TraceAnnotation("serve.step.dispatch"):
                logits, self.cache = self._decode(self.params, self.cache, tok, pos)
            with TraceAnnotation("serve.step.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with TraceAnnotation("serve.step.feedback"):
                self._feedback(nxt)
            self.step_count += 1

    def _inputs(self):
        """Each slot's next token and position, on the device."""
        tokens, positions = [], []
        for s in self.slots:
            tokens.append(PAD if s.req is None else s.req.output[-1])
            positions.append(s.pos)
        tok = jnp.asarray(tokens, jnp.int32)[:, None]
        pos = jnp.asarray(positions, jnp.int32)
        if self.cfg.use_mrope:
            pos = jnp.broadcast_to(pos[:, None], (self.max_slots, 3))
        return tok, pos

    def _feedback(self, nxt):
        """Advance each busy slot by the token the step made for it."""
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            s.pos += 1
            _emit(s.req, int(nxt[i]))
            self._maybe_finish(s)

    def run(self, max_steps: int = 10_000) -> list:
        """Drain the queue; returns requests finished since the last run()
        (each request is returned exactly once across repeated
        submit/run cycles, and handed-back requests stop being tracked)."""
        finished: list[Request] = []

        def collect():
            done = [r for r in self.requests if r.done]
            if done:
                finished.extend(done)
                self.requests = [r for r in self.requests if not r.done]

        collect()                      # finished via manual step()s pre-run
        start = self.step_count        # max_steps bounds THIS call, not the
        while (self.queue or self.active) \
                and self.step_count - start < max_steps:   # batcher lifetime
            self.step()
            collect()
        return finished
