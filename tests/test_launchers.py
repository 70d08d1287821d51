"""Launcher / registry / profile / report-layer tests."""
import json
import subprocess
import sys

import pytest

from repro.clouds.profiles import PROFILES, get_profile
from repro.configs import registry
from repro.launch import report


def test_registry_normalization_accepts_display_names():
    for alias in ("xlstm-1.3b", "zamba2-1.2b", "granite-moe-3b-a800m",
                  "deepseek-v2-lite-16b", "xlstm_1_3b"):
        cfg = registry.get_config(alias)
        assert cfg.n_layers > 0


def test_registry_unknown_arch_raises():
    with pytest.raises(KeyError, match="unknown arch"):
        registry.get_config("gpt-17")


def test_all_archs_have_smoke_and_full():
    for arch in registry.list_archs():
        full = registry.get_config(arch)
        smoke = registry.get_smoke_config(arch)
        assert smoke.family == full.family
        assert smoke.n_layers <= 4
        assert smoke.d_model <= 512


def test_input_shapes_match_assignment():
    s = registry.INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)
    assert s["decode_32k"].kind == "decode" and s["long_500k"].kind == "decode"


def test_cloud_profiles_cover_paper_platforms():
    assert set(PROFILES) == {"gcp", "ibm", "baremetal", "k8s"}
    gcp, ibm = get_profile("gcp"), get_profile("ibm")
    assert ibm.network_rtt_s < gcp.network_rtt_s      # paper §7(1)
    assert ibm.startup_s > gcp.startup_s              # paper §7(2)
    assert gcp.hardware.peak_flops_bf16 == 197e12
    assert gcp.hardware.hbm_bw == 819e9
    assert gcp.hardware.ici_bw == 50e9


def test_report_tables_from_records(tmp_path):
    rec = {"arch": "a", "shape": "train_4k", "mesh": "single", "status": "ok",
           "chips": 256, "lower_s": 1.0, "compile_s": 2.0,
           "roofline": {"compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.5,
                        "bound_s": 2.0, "dominant": "memory", "flops": 1e12,
                        "bytes_accessed": 1e12, "coll_bytes": 1e10, "chips": 256},
           "useful_flops_ratio": 0.5,
           "collectives": {"per_kind_counts": {"all-reduce": 3}}}
    skip = {"arch": "b", "shape": "long_500k", "mesh": "single",
            "status": "skipped", "reason": "pure full-attention arch"}
    (tmp_path / "a_train_4k_single.json").write_text(json.dumps(rec))
    (tmp_path / "b_long_500k_single.json").write_text(json.dumps(skip))
    recs = report.load(str(tmp_path), "single")
    assert len(recs) == 2
    table = report.roofline_table(recs)
    assert "**memory**" in table and "skipped" in table
    dtable = report.dryrun_table(recs)
    assert "256" in dtable


def test_mesh_shapes():
    from repro.launch import mesh as mesh_mod
    import jax
    m = mesh_mod.make_local_mesh()
    assert m.axis_names == ("data", "model")
    assert m.size == len(jax.devices())


@pytest.mark.parametrize("cli", [
    ["-m", "repro.launch.serve", "--arch", "whisper-base", "--requests", "6",
     "--gen-tokens", "2", "--max-batch", "4"],
])
def test_serve_cli_end_to_end(cli):
    r = subprocess.run([sys.executable] + cli, capture_output=True, text=True,
                       timeout=900,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd=__import__("os").path.dirname(
                           __import__("os").path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-500:]
    out = json.loads(r.stdout)
    assert out["n"] == 6


def _entries(d):
    return sorted(p.name for p in d.iterdir()) if d.is_dir() else []


def test_compile_cache_follows_env_dir_and_nowhere_else(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there, none in the
    checkout's own cache directory."""
    import os
    from repro.launch.compile_cache import DEFAULT_DIR
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env_dir = tmp_path / "cc"
    before = _entries(DEFAULT_DIR)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=os.path.dirname(os.path.dirname(__file__)),
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(env_dir),
             # cache even this tiny program
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert r.returncode == 0, r.stderr[-500:]
    assert r.stdout.strip() == str(env_dir)
    assert _entries(env_dir), "no cache entry written to JAX_COMPILATION_CACHE_DIR"
    assert _entries(DEFAULT_DIR) == before


def test_compile_cache_defaults_to_fixed_ignored_dir_in_checkout(monkeypatch):
    import os
    from pathlib import Path

    import jax
    from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache
    root = Path(__file__).resolve().parents[1]
    assert DEFAULT_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
