"""BENCHMARK.json against the benchmark's own rules: the keys, names and
units, and a file behind every name."""
import re

import pytest

from bench import harness
from bench.tests import smoke

SPEC = harness.load_json(smoke.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_command_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    cells = 24
    assert (2 + 14 * cells) * (SPEC["run_seconds"] + 60) + cells * 180 + 1200 <= 43200


def test_configs_are_files_under_paths_and_used():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        conf = harness.load_json(smoke.ROOT / c["file"])
        assert conf["reduced"] == c["reduced"]
        assert (smoke.BENCH / "configs" / f"{c['name']}.py").is_file()
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200


def test_cells_name_their_files_and_report_what_they_must():
    pairs = set()
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = harness.load_json(smoke.BENCH / "traffic" / f"{w['traffic']}.json")
        assert (smoke.BENCH / "drivers" / f"{traffic['kind']}.py").is_file()
        cell = harness.Cell(w["name"], smoke.ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_have_readers_and_valid_fields(kind):
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC[kind]:
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == keys
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert (smoke.BENCH / "metrics" / f"{m['name']}.py").is_file()
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
