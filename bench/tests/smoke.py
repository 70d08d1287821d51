"""A copy of the benchmark at a size the CPU runs in seconds: the same
harness, drivers, readers and references, with each configuration shrunk
(every width cut, which a cell never does) and its traffic shortened."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

H2O_SMOKE = {
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512,
}
H2O_PROGRAM = {"n_layers": 2, "d_model": 128, "d_ff": 256, "n_heads": 8,
               "n_kv_heads": 2, "head_dim": 16, "vocab_size": 512}
BATCH_DECODE_SMOKE = {
    "requests": 16, "backlog": 2, "warm_steps": 2, "first_wave_outputs": [6, 12],
    "sample_requests": 2,
    "prompt_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 8, "max": 32, "multiple": 8},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.4,
                      "min": 6, "max": 24, "multiple": 1},
}


def make_root(tmp: Path, *, logit_gap=1.0) -> Path:
    """A checkout-like tree under tmp: BENCHMARK.json and bench/, with the
    h2o configuration and batch_decode traffic at smoke size, and a peaks
    entry for the CPU so that the readers have peaks to divide by."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cpath = root / "bench" / "configs" / "h2o_danube_3_4b.json"
    conf = json.loads(cpath.read_text())
    conf.update(H2O_SMOKE)
    conf["program"]["replace"].update(H2O_PROGRAM)
    conf["serving"] = {"max_slots": 2, "max_len": 64, "prefill_chunk": 16}
    conf["correct"]["logit_gap"] = logit_gap
    cpath.write_text(json.dumps(conf))
    tpath = root / "bench" / "traffic" / "batch_decode.json"
    traffic = json.loads(tpath.read_text())
    traffic.update(BATCH_DECODE_SMOKE)
    tpath.write_text(json.dumps(traffic))
    ppath = root / "bench" / "peaks.json"
    peaks = json.loads(ppath.read_text())
    peaks["kinds"]["cpu"] = dict(peaks["kinds"]["TPU v5 lite"])
    ppath.write_text(json.dumps(peaks))
    return root
