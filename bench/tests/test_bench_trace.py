"""The trace reduction, against totals worked out by hand."""
import pytest

from bench import tracing

MS = 1_000_000  # ns


def test_busy_idle_ops_and_labelled_gaps():
    # window 0-100 ms; ops at 10-30, 30-40 (back to back), 60-70, and 95-120
    # (clipped to 95-100): busy = 30 + 10 + 5 = 45 ms, idle 55 ms in gaps
    # 0-10, 40-60, 70-95, labelled by the innermost span around each midpoint
    events = {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 30 * MS, 10 * MS),
            ("fusion.1", 60 * MS, 10 * MS), ("copy.3", 95 * MS, 25 * MS)]},
        "spans": [("bench.window", 0, 100 * MS),
                  ("bench.step.decode", 0, 45 * MS),
                  ("bench.step.admit", 45 * MS, 55 * MS)],
    }
    r = tracing.reduce(events)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.045)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.030, "fusion.2": 0.010, "copy.3": 0.005})
    assert r["idle_gaps"] == [["step.admit", pytest.approx(0.025)],
                              ["step.admit", pytest.approx(0.020)],
                              ["step.decode", pytest.approx(0.010)]]
    assert r["collective_s"] == 0 and r["exposed_collective_s"] == 0


def test_collectives_and_their_exposed_part_averaged_over_devices():
    # device 0: all-reduce 0-40, compute 30-50 -> collective 40, exposed 30,
    # busy 50.  device 1: all-gather 0-10, nothing else -> 10, 10, busy 10.
    events = {
        "devices": {
            "/device:TPU:0": [("all-reduce.1", 0, 40 * MS),
                              ("fusion.9", 30 * MS, 20 * MS)],
            "/device:TPU:1": [("all-gather-start.2", 0, 10 * MS)]},
        "spans": [("bench.window", 0, 100 * MS)],
    }
    r = tracing.reduce(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.050 + 0.010) / 2)
    assert r["collective_s"] == pytest.approx((0.040 + 0.010) / 2)
    assert r["exposed_collective_s"] == pytest.approx((0.030 + 0.010) / 2)
    assert r["idle_gaps"][0] == ["outside", pytest.approx(0.090)]


def test_a_trace_without_a_window_span_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce({"devices": {}, "spans": [("bench.step.decode", 0, 1)]})


def test_a_recorded_cpu_trace_reads_its_spans_and_ops():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    rec = tracing.Recorder()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step.decode"):
                f(x).block_until_ready()
    events = rec.read()
    names = [s[0] for s in events["spans"]]
    assert names.count("bench.step.decode") == 3 and "bench.window" in names
    r = tracing.reduce(events)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"]


def test_nested_ops_count_their_own_time_and_names_are_short():
    # a loop 0-100 ms holding two body ops 10-30 and 50-60: the loop keeps
    # 70 ms of its own
    loop = "%while.3 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), body=%b"
    body = "%fusion.4 = f32[8,2048]{1,0:T(8,128)} fusion(bf16[8]{0} %p), kind=kLoop"
    events = {"devices": {"/device:TPU:0": [
        (loop, 0, 100 * MS), (body, 10 * MS, 20 * MS), (body, 50 * MS, 10 * MS)]},
        "spans": [("bench.window", 0, 100 * MS)]}
    r = tracing.reduce(events)
    assert dict(r["device_ops"]) == pytest.approx(
        {"while.3": 0.070, "fusion.4 f32[8,2048]": 0.030})
    assert r["busy_s"] == pytest.approx(0.100)
