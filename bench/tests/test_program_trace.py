"""The program's spans, program names and scopes in a trace, and their
reduction: a smoke-size ContinuousBatcher traced on the CPU, hand-built
event lists with totals worked out by hand, and the metric that reads the
batcher's own clock."""
import argparse

import jax
import pytest

from bench import harness, program_trace, tracing
from bench.tests import smoke

MS = 1_000_000  # ns
DEC = program_trace.DECODE_MODULE


# -- a traced batcher ----------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    from repro.configs import registry
    from repro.models import lm
    cfg = registry.get_smoke_config("h2o_danube_3_4b")
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


PROMPTS = [[5, 17, 99, 3, 8, 1, 2, 40, 41], [200, 41], [7] * 12, [3, 1, 4, 1, 5]]


def _serve(model, record):
    """Four requests through a 2-slot batcher with chunked prefill, the
    steps traced when `record`; returns (outputs, events, steps, rids
    admitted while recording)."""
    from repro.serving.continuous import ContinuousBatcher
    cfg, params = model
    cb = ContinuousBatcher(cfg, params, max_slots=2, max_len=64, prefill_chunk=4)
    reqs = [cb.submit(p, max_new=5) for p in PROMPTS]
    cb.step()                                 # compiles, not recorded
    rec = tracing.Recorder() if record else None
    steps = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while cb.queue or cb.active:
            cb.step()
            steps += 1
    events = None
    if record:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tracing, "events_from_xplane", program_trace.events_from_xplane)
            events = rec.read()
    admitted = [r.rid for r in reqs if r.admitted_step >= 1]
    return [r.output for r in reqs], events, steps, admitted


@pytest.fixture(scope="module")
def traced(model):
    return _serve(model, record=True)


def _named(events, name):
    return sorted((x for x in events["spans"] if x[0] == name), key=lambda x: x[1])


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_a_step_and_an_admission_each_record_their_span(traced):
    _, events, steps, admitted = traced
    assert len(_named(events, "serve.step")) == steps
    admits = _named(events, "serve.admit")
    assert sorted(a[3]["rid"] for a in admits) == sorted(admitted) and admitted
    prefills = _named(events, "serve.prefill")
    assert [(p[3]["rid"], p[3]["tokens"]) for p in prefills] == \
        [(rid, len(PROMPTS[rid])) for rid in sorted(admitted)]
    for name in ("serve.reset_row", "serve.prefill", "serve.scatter_row",
                 "serve.admit.sync"):
        kids = _named(events, name)
        assert len(kids) == len(admits)
        assert all(any(_within(k, a) for a in admits) for k in kids)
    assert all(any(_within(a, s) for s in _named(events, "serve.step")) for a in admits)


def test_step_children_nest_and_decode_ops_run_between_dispatch_and_sync(traced):
    _, events, steps, _ = traced
    step_spans = _named(events, "serve.step")
    for name in ("serve.step.inputs", "serve.step.dispatch", "serve.step.sync",
                 "serve.step.feedback"):
        kids = _named(events, name)
        assert len(kids) == steps
        assert all(any(_within(k, s) for s in step_spans) for k in kids)
    (ops,) = events["devices"].values()
    dec = [o for o in ops if o[3] == DEC]
    assert dec and {o[3] for o in ops} >= {DEC, "jit_serve_prefill"}
    runs = [(d[1], s[1] + s[2]) for d, s in zip(_named(events, "serve.step.dispatch"),
                                               _named(events, "serve.step.sync"))]
    assert all(any(a <= o[1] and o[1] + o[2] <= b for a, b in runs) for o in dec)


def test_tokens_are_the_same_with_the_profiler_on_and_off(model, traced):
    assert _serve(model, record=False)[0] == traced[0]


# -- the reduction on hand-built events ---------------------------------------

def _events():
    # window 0-100 ms; one admitting step 0-40 (admit 4-24 holding prefill
    # 5-21; sync 30-38) and one decode step 50-90 (inputs 50-52, dispatch
    # 52-54, sync 54-86).  Device: prefill 5-20 (no scope), decode ops 24-30
    # (attention), a loop 56-84 holding 56-70 (attention) and 70-84 (ffn),
    # so the loop keeps none of its own.
    sp = [("bench.window", 0, 100 * MS, {}),
          ("bench.step.admit", 0, 40 * MS, {}),
          ("serve.step", 0, 40 * MS, {}),
          ("serve.admit", 4 * MS, 20 * MS, {"rid": 7}),
          ("serve.prefill", 5 * MS, 16 * MS, {"rid": 7, "tokens": 64}),
          ("serve.step.sync", 30 * MS, 8 * MS, {}),
          ("bench.step.decode", 40 * MS, 51 * MS, {}),
          ("serve.step", 50 * MS, 40 * MS, {}),
          ("serve.step.inputs", 50 * MS, 2 * MS, {}),
          ("serve.step.dispatch", 52 * MS, 2 * MS, {}),
          ("serve.step.sync", 54 * MS, 32 * MS, {})]
    ops = [("%fusion.1 = bf16[1,64]{1,0} fusion()", 5 * MS, 15 * MS, "jit_serve_prefill"),
           ("%fusion.2 = f32[8]{0} fusion()", 24 * MS, 6 * MS, DEC),
           ("%while.3 = () while()", 56 * MS, 28 * MS, DEC),
           ("%fusion.2 = f32[8]{0} fusion()", 56 * MS, 14 * MS, DEC),
           ("%fusion.5 = f32[8]{0} fusion()", 70 * MS, 14 * MS, DEC)]
    return {"devices": {"/device:TPU:0": ops}, "spans": sp}


PATH = "jit(serve_decode)/while/body/closed_call/{}/dot_general"
OP_NAMES = {"fusion.2": PATH.format("attention"), "fusion.5": PATH.format("ffn"),
            "while.3": "jit(serve_decode)/while"}


def test_spans_modules_scopes_and_innermost_labels():
    r = program_trace.reduce(_events(), OP_NAMES)
    assert r["spans"]["serve.step"] == pytest.approx(
        {"count": 2, "total_s": 0.080, "self_s": 0.080 - 0.020 - 0.008 - 0.036})
    assert r["spans"]["serve.admit"] == pytest.approx(
        {"count": 1, "total_s": 0.020, "self_s": 0.004})
    assert r["modules"] == pytest.approx({DEC: 0.034, "jit_serve_prefill": 0.015})
    assert r["scopes"] == pytest.approx({"attention": 0.020, "ffn": 0.014,
                                         program_trace.NO_SCOPE: 0.0})
    # idle 0-5 (in serve.step), 20-24 (serve.admit), 30-56 (midpoint 43 in
    # bench.step.decode only) and 84-100 (midpoint 92 in no span)
    assert r["idle_gaps"] == [["step.decode", pytest.approx(0.026)],
                              ["outside", pytest.approx(0.016)],
                              ["serve.step", pytest.approx(0.005)],
                              ["serve.admit", pytest.approx(0.004)]]
    assert r["idle_labels"] == pytest.approx(
        {"step.decode": 0.026, "outside": 0.016, "serve.step": 0.005,
         "serve.admit": 0.004})
    # the one step without an admission: 40 ms less its 32 ms sync
    assert r["step_host_s"] == pytest.approx(0.008)
    assert program_trace.numbers(r) == pytest.approx(
        {"admit_ms": 20.0, "step_host_ms": 8.0, "decode_device_ms": 34.0,
         "decode_attention_ms": 20.0})


@pytest.mark.parametrize("serve_spans", [False, True])
def test_the_harness_keys_are_tracing_reduce_s_own(serve_spans):
    ev = _events()
    if not serve_spans:
        ev["spans"] = [x for x in ev["spans"] if x[0].startswith("bench.")]
    old = tracing.reduce({"devices": {k: [o[:3] for o in v] for k, v in ev["devices"].items()},
                          "spans": [x[:3] for x in ev["spans"] if x[0].startswith("bench.")]})
    r = program_trace.reduce(ev, OP_NAMES)
    for k in ("devices", "window_s", "busy_s", "collective_s",
              "exposed_collective_s", "device_ops"):
        assert r[k] == old[k]
    if not serve_spans:
        assert r["idle_gaps"] == old["idle_gaps"] and r["spans"] == {}


@pytest.mark.parametrize("path,scope", [
    ("jit(serve_decode)/while/body/closed_call/attention/bsd,dhk->bshk/dot_general",
     "attention"),
    ("jit(serve_decode)/while/body/closed_call/ffn/mul", "ffn"),
    ("jit(serve_decode)/while/body/closed_call/jit(remainder)/rem", program_trace.NO_SCOPE),
    ("jit(serve_decode)/argmax", program_trace.NO_SCOPE),
    ("", program_trace.NO_SCOPE)])
def test_top_scope(path, scope):
    assert program_trace.top_scope(path) == scope


def test_op_names_from_compiled_hlo_by_instruction():
    # a fusion without metadata of its own takes its computation's root's
    hlo = ('%fused_computation.4 (param_0: f32[8]) -> f32[8] {\n'
           '  %param_0 = f32[8]{0} parameter(0)\n'
           '  ROOT %exp.1 = f32[8]{0} exponential(%param_0), '
           'metadata={op_name="jit(serve_decode)/ffn/exp"}\n'
           '}\n\n'
           'ENTRY %main.9 (p: f32[8]) -> f32[8] {\n'
           '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
           'metadata={op_name="jit(serve_decode)/attention/mul" stack_frame_id=3}\n'
           '  %fusion.7 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.4\n'
           '  ROOT %copy.9 = f32[8]{0} copy(%x), metadata={op_name="jit(serve_decode)/ffn/copy"}\n'
           '}\n')
    names = program_trace.hlo_op_names(hlo)
    assert names == {"fusion.2": "jit(serve_decode)/attention/mul",
                     "fusion.7": "jit(serve_decode)/ffn/exp",
                     "exp.1": "jit(serve_decode)/ffn/exp",
                     "copy.9": "jit(serve_decode)/ffn/copy"}
    assert program_trace.instruction("%fusion.7 = f32[8]{0} fusion(%fusion.2)") == "fusion.7"


# -- the smoke-size cell, traced, read from the program's spans ---------------

def test_a_traced_smoke_run_gives_every_program_number(tmp_path):
    root = smoke.make_root(tmp_path, logit_gap=0.1)
    cell = harness.Cell(harness.load_json(root / "BENCHMARK.json")["workloads"][0]["name"], root)
    out, r = program_trace.traced_run(cell, seed=2 ** 33 + 11, seconds=1.0,
                                      platform="cpu")
    got = program_trace.numbers(r)
    assert set(got) == {"admit_ms", "step_host_ms", "decode_device_ms",
                        "decode_attention_ms"}
    assert 0 < got["decode_attention_ms"] < got["decode_device_ms"]
    assert {"attention", "ffn"} <= set(r["scopes"])
    # the batcher's clock and its span time the same admissions
    assert out["result"]["metrics"]["admit_ms.batch"]["value"] == \
        pytest.approx(got["admit_ms"], rel=0.25)


# -- the metric that reads the batcher's clock --------------------------------

def test_admit_ms_reads_the_window_s_admissions():
    read = harness.load_module(smoke.BENCH / "metrics" / "admit_ms.batch.py").read

    def req(t_admit, t_first):
        return argparse.Namespace(t_admit=t_admit, t_first=t_first)
    rec = {"t0": 10.0, "t1": 20.0, "info": {
        1: {"req": req(9.0, 9.5)},        # before the window
        2: {"req": req(11.0, 11.020)},
        3: {"req": req(15.0, 15.040)},
        4: {"req": req(19.99, None)},     # no first token yet
        5: {"req": argparse.Namespace()}}}  # a program without these times
    assert read(rec) == pytest.approx(30.0)
    assert read({"t0": 0.0, "t1": 1.0, "info": {5: {"req": argparse.Namespace()}}}) is None


@pytest.mark.parametrize("start,module", [(0, "jit_a"), (9, "jit_a"), (10, "jit_b"),
                                          (20, ""), (30, "jit_c"), (45, "")])
def test_an_op_without_a_module_stat_takes_the_program_run_around_it(start, module):
    # a TPU plane's "XLA Modules" line: runs 0-10, 10-20 and 30-40
    runs = [(0, 10, "jit_a"), (10, 20, "jit_b"), (30, 40, "jit_c")]
    op = program_trace._device_op("%fusion.1 = f32[8]{0} fusion()", start, 1, {}, runs)
    assert op[3] == module
