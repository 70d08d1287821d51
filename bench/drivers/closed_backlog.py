"""Closed loop with a backlog, through ContinuousBatcher.

The traffic file gives the lengths as distributions.  Every seed serves
the same set of lengths: `requests` evenly spaced quantiles of each
distribution, clipped and rounded to its `multiple`.  The seed only orders
them and draws the token ids, so runs on different seeds do the same work.
Decoding is greedy with no end-of-sequence token, so a request always
yields its full output length.

The queue always holds `backlog` waiting requests, so every slot is busy
in every step.  Set-up makes the weights from the seed, prefills one
prompt of each length in the set (so every chunk shape and offset
computation is compiled), fills every slot with the first wave, whose
staggered output lengths spread the slots' turnovers over the window, and
runs `warm_steps` more steps.  The window then runs steps for --seconds.  A token's time is when the step that made
it returns to the host: `step()` ends in a host copy of its argmax.

`correct`: once the window has closed and the batcher is freed, the plain
reference reads a seeded sample of the requests served in the window, the
one with most tokens among them, and reports the widest gap by which a
served token's reference logit lies below the reference's best there.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
from statistics import NormalDist

import numpy as np

from bench import harness, tracing


def lengths(dist: dict, n: int) -> list:
    """n evenly spaced quantiles of a lognormal, clipped to [min, max] and
    rounded to a multiple."""
    m = dist.get("multiple", 1)
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        x = int(round(x / m)) * m
        out.append(min(max(x, dist["min"]), dist["max"]))
    return out


def make_requests(traffic: dict, vocab: int, rng) -> tuple:
    """The first wave and the pool, each [(prompt token ids, output
    length)] in the seed's order.  The first wave fills the slots in set-up;
    its output lengths are the file's `first_wave_outputs`, spread so that
    the slots turn over at different steps, as in a loop that has run for
    long, and its prompts the quantiles of the prompt distribution."""
    def draw(prompts, outputs):
        return [(rng.integers(0, vocab, int(p)).tolist(), int(g))
                for p, g in zip(rng.permutation(prompts), outputs)]
    first = traffic["first_wave_outputs"]
    n = traffic["requests"]
    return (draw(lengths(traffic["prompt_tokens"], len(first)), first),
            draw(lengths(traffic["prompt_tokens"], n),
                 rng.permutation(lengths(traffic["output_tokens"], n))))


class Loop:
    """Keeps the backlog full, steps the batcher, and records when each
    token became visible."""

    def __init__(self, cb, first, pool, backlog: int, trace: bool):
        self.cb = cb
        self.first = first
        self.pool = pool
        self.backlog = backlog
        self.trace = trace
        self.next = 0
        self.live: list = []              # submitted, not yet done
        self.info: dict = {}              # rid -> {"prompt", "times"}
        self.steps: list = []

    def top_up(self):
        while len(self.cb.queue) < self.backlog:
            i = self.next - len(self.first)
            prompt, n_out = self.pool[i % len(self.pool)] if i >= 0 \
                else self.first[self.next]
            self.next += 1
            r = self.cb.submit(prompt, n_out)
            self.live.append(r)
            self.info[r.rid] = {"req": r, "prompt": len(prompt), "times": []}

    def step(self):
        self.top_up()
        cb = self.cb
        admit = cb.active < cb.max_slots and len(cb.queue) > 0
        before = [(r, len(r.output)) for r in self.live]
        pf0 = cb.prefill_stats["tokens"]
        span = ("bench.step.admit" if admit else "bench.step.decode") \
            if self.trace else None
        t0 = time.perf_counter()
        with _annotate(span):
            cb.step()
        t1 = time.perf_counter()
        contexts, prefills = [], []
        for r, n0 in before:
            n1 = len(r.output)
            if n1 == n0:
                continue
            p = self.info[r.rid]["prompt"]
            if n0 == 0:
                prefills.append(p)
            contexts.extend(p + k - 1 for k in range(max(n0, 1), n1))
            self.info[r.rid]["times"].extend([t1] * (n1 - n0))
        self.live = [r for r in self.live if not r.done]
        self.steps.append({"t0": t0, "t1": t1, "admit": admit,
                           "prefill_tokens": cb.prefill_stats["tokens"] - pf0,
                           "prefills": prefills, "contexts": contexts})


def _annotate(name):
    if name is None:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _sample(info: dict, served: list, k: int, rng) -> list:
    """A seeded sample of k of the served requests, with the one that has
    most tokens among them."""
    if not served:
        return []
    longest = max(served, key=lambda rid: (len(info[rid]["req"].output), -rid))
    rest = [rid for rid in served if rid != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest and k > 1 else []
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def reference_gaps(model, conf, weights, seqs, length: int, control: bool):
    """For each sequence (prompt ids, served ids): the widest gap by which a
    served token's reference logit lies below the reference's best, and,
    with `control`, the same for the token the fp8 reference puts first."""
    import jax
    import jax.numpy as jnp

    toks = np.zeros((len(seqs), length), np.int32)
    mask = np.zeros((len(seqs), length - 1), bool)
    for i, (prompt, out) in enumerate(seqs):
        full = list(prompt) + list(out)
        toks[i, :len(full)] = full
        mask[i, len(prompt) - 1:len(full) - 1] = True

    def gaps(w, toks):
        lg = model.forward(w, conf, toks)[:, :-1]
        best = lg.max(-1)
        g = best - jnp.take_along_axis(lg, toks[:, 1:, None], -1)[..., 0]
        if not control:
            return g, g
        c = jnp.argmax(model.forward(w, conf, toks, fp8=True)[:, :-1], -1)
        return g, best - jnp.take_along_axis(lg, c[..., None], -1)[..., 0]

    g, gc_ = jax.jit(gaps)(weights, jnp.asarray(toks))
    g, gc_ = np.asarray(g), np.asarray(gc_)
    return float(g[mask].max()), (float(gc_[mask].max()) if control else None)


def run(cell, args, devs, t_proc: float, counter, control: bool = False) -> dict:
    """One run; returns the record the metric readers read."""
    import jax
    from repro.models import lm
    from repro.serving.continuous import ContinuousBatcher

    conf, traffic, model = cell.config, cell.traffic, cell.model
    cfg = harness.program_config(conf)
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    weights = harness.make_weights(shapes, model.WEIGHTS, key)
    sv = conf["serving"]
    cb = ContinuousBatcher(cfg, weights, max_slots=sv["max_slots"],
                           max_len=sv["max_len"], prefill_chunk=sv["prefill_chunk"])
    first, pool = make_requests(traffic, conf["vocab_size"], rng)
    for n in sorted({len(p) for p, _ in first + pool}):
        cb.prefill([1] * n)               # every prefill shape of the mix
    loop = Loop(cb, first, pool, traffic["backlog"], args.trace)
    while len(loop.steps) < 1 or loop.steps[-1]["admit"]:
        loop.step()                       # until every slot is filled
    for _ in range(traffic["warm_steps"]):
        loop.step()
    jax.block_until_ready(cb.cache)
    n_warm = len(loop.steps)

    rec_trace = tracing.Recorder() if args.trace else None
    counter.active = True
    t0 = time.perf_counter()
    with _annotate("bench.window" if args.trace else None):
        while time.perf_counter() - t0 < args.seconds:
            loop.step()
    t1 = loop.steps[-1]["t1"]
    counter.active = False
    trace = tracing.reduce(rec_trace.read()) if rec_trace else None
    mem = harness.memory_peak(devs)

    # the program's state goes before the reference runs
    info, steps = loop.info, loop.steps[n_warm:]
    del loop, cb
    gc.collect()
    served = sorted(rid for rid, v in info.items() if any(t > t0 for t in v["times"]))
    sample = _sample(info, served, traffic["sample_requests"], rng)
    seqs = [(info[rid]["req"].prompt, info[rid]["req"].output) for rid in sample]
    bad = sum(1 for v in info.values()
              if any(not 0 <= t < conf["vocab_size"] for t in v["req"].output))
    t_ref = time.perf_counter()
    gap, gap_c = reference_gaps(model, conf, weights, seqs, sv["max_len"], control)
    harness.log(f"reference: {time.perf_counter() - t_ref:.2f} s")
    harness.log(f"window {t1 - t0:.3f} s, {len(steps)} steps "
                f"({sum(s['admit'] for s in steps)} admitting), "
                f"{len(served)} requests served, compared {len(sample)} "
                f"({sum(len(s[1]) for s in seqs)} served tokens)")
    return {"t_proc": t_proc, "t0": t0, "t1": t1, "steps": steps, "info": info,
            "conf": conf, "model": model, "trace": trace, "memory_peak": mem,
            "compiles": counter.count, "attempted": len(served), "failed": bad,
            "checks": {"logit_gap": {"value": gap, "limit": conf["correct"]["logit_gap"]}},
            "control_gap": gap_c}
