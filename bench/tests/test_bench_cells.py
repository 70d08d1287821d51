"""Every cell end to end on the CPU at smoke size, with the platform check
steered from here; the last line's keys; cells and metrics added as files
alone; and the runs that must fail."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench import run as bench_run
from bench.tests import smoke

# Smoke-size limit of the logit gap: sound runs read 0 to 0.025 over seeds
# 0-7 on the CPU, the fp8 control 0.182 to 0.498 (smoke readings, see
# PERF.md); 0.1 lies between with room on both sides.
SMOKE_LOGIT_GAP = 0.1
CELLS = [w["name"] for w in harness.load_json(smoke.ROOT / "BENCHMARK.json")["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("bench"), logit_gap=SMOKE_LOGIT_GAP)


def measure(root, cell, trace=0, seed=2 ** 33 + 5, seconds=1.0):
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_run.measure(harness.Cell(cell, root), args, platform="cpu")


def last_line(out, capsys):
    harness.emit(out["result"], out["checks"])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_at_smoke_size(root, cell, trace, capsys):
    line = last_line(measure(root, cell, trace), capsys)
    assert set(line) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in harness.Cell(cell, root).metrics(trace)}
    assert set(line["metrics"]) == want
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    if trace:
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert 0 < len(line["breakdown"]["device_ops"]) <= 10
        assert 0 < len(line["breakdown"]["idle_gaps"]) <= 10
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def test_a_cell_and_a_metric_added_as_files_alone_are_found(root, tmp_path, capsys):
    new = tmp_path / "root"
    shutil.copytree(root, new)
    traffic = json.loads((new / "bench/traffic/batch_decode.json").read_text())
    traffic["prompt_tokens"]["max"] = 16
    (new / "bench/traffic/short_prompts.json").write_text(json.dumps(traffic))
    (new / "bench/metrics/steps_in_window.short.py").write_text(
        "def read(rec):\n    return float(len(rec['steps']))\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "h2o.short", "config": "h2o_danube_3_4b",
                              "traffic": "short_prompts", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_in_window.short", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "output_tokens_per_s",
                              "workloads": ["h2o.short"]})
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m and m["name"] != "steps_in_window.short":
            m["workloads"].append("h2o.short")
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    line = last_line(measure(new, "h2o.short", trace=1), capsys)
    assert line["correct"] is True
    assert line["metrics"]["steps_in_window.short"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    r = _cli(smoke.ROOT)
    assert r.returncode != 0 and not _has_result(r.stdout)
    assert "not 'tpu'" in r.stderr


def test_a_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copy(smoke.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0 and not _has_result(r.stdout)
