"""chip_smoke.py's phases on the CPU at smoke configs.

The script itself refuses any platform but a TPU; here its phase functions
are called directly, and the four-chip phase runs on four virtual CPU
devices in a subprocess that never looks for a TPU.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import registry

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    return {**os.environ, "PYTHONPATH": str(ROOT / "src"),
            "JAX_PLATFORMS": "cpu", **extra}


def test_serve_phase_at_smoke_config():
    """Prompts longer than the smoke window (16) wrap the ring cache, so the
    prefill-vs-forward check covers the windowed prefill."""
    cs = _load()
    cfg = registry.get_smoke_config("h2o_danube_3_4b")
    out = cs.serve_phase(cfg, max_slots=2, max_len=64, prefill_chunk=8,
                         n_requests=5, prompt_lens=(8, 24, 40), max_new=4)
    assert out["requests"] == 5 and out["tokens"] == 20
    assert out["logit_err"] <= out["logit_tol"]


def test_device_check_accepts_only_the_expected_platform():
    cs = _load()
    assert cs.device_info(expect="cpu")["platform"] == "cpu"
    with pytest.raises(SystemExit):
        cs.device_info()


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_script_fails_without_a_tpu(args):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                       capture_output=True, text=True, timeout=300,
                       cwd=ROOT, env=_cpu_env())
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_four_chip_phase_on_four_virtual_devices():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(ROOT / 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)\n"
        "from repro.configs import registry\n"
        "full = registry.get_smoke_config('zamba2_1_2b').replace(dtype='bfloat16')\n"
        "out = cs.four_chip_phase(full, full.replace(n_layers=2), batch=4, seq=32,"
        " steps=2)\n"
        "print('FOUR_OK', out['loss_one'], out['loss_mesh'])\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert "FOUR_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
