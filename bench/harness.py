"""What every run shares: finding a cell's files by name, the device check,
the peaks table, the compile counter, and the run's last lines.

A cell in BENCHMARK.json names a configuration and a traffic mix.  The
harness finds them as files:

    bench/configs/<config>.json    sizes as run (+ the limits of `correct`)
    bench/configs/<config>.py      the plain reference and the counts
    bench/traffic/<traffic>.json   the mix; its "kind" names the driver
    bench/drivers/<kind>.py        the general generator and window loop
    bench/metrics/<metric>.py      one reader per metric: read(rec)

so a later change adds a cell or a metric as new files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# fired by JAX once per executable built, whether compiled or read from
# the persistent cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot give a result; run.py exits non-zero on it."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"no file {path}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's workloads, with its files loaded."""

    def __init__(self, name: str, root: Path = ROOT):
        spec = load_json(root / "BENCHMARK.json")
        self.base = base = root / "bench"
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.cell = cells[name]
        self.chips = int(self.cell["chips"])
        self.config = load_json(base / "configs" / f"{self.cell['config']}.json")
        self.model = load_module(base / "configs" / f"{self.cell['config']}.py")
        self.traffic = load_json(base / "traffic" / f"{self.cell['traffic']}.json")
        self.driver = load_module(base / "drivers" / f"{self.traffic['kind']}.py")

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end

    def reader(self, metric: str):
        return load_module(self.base / "metrics" / f"{metric}.py").read


def require_devices(chips: int, platform: str = "tpu") -> list:
    """The first `chips` devices; BenchError unless they are `platform`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"JAX's device is {devs[0].platform!r}, not "
                         f"{platform!r}: nothing is measured on it")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str, table: Path) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind is
    an error, never a default."""
    kinds = load_json(table)["kinds"]
    if device_kind not in kinds:
        raise BenchError(f"device_kind {device_kind!r} is not in "
                         f"{table.name} (have {sorted(kinds)})")
    return kinds[device_kind]


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) while `active` is set."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.names: list = []
        self.active = False
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.active and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.names.append(kw.get("fun_name", "?"))

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on_event)


def memory_peak(devs) -> int | None:
    """peak_bytes_in_use of the fullest device, where the backend reports it."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return None if None in peaks_ else max(peaks_)


def log(msg: str) -> None:
    print(msg, flush=True)


def emit(result: dict, checks: dict) -> None:
    """The run's last lines: each compared number beside its limit on
    standard error, and the result as the last line of standard output,
    with the same numbers under "checks", its last key."""
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)


def program_config(conf: dict):
    """The program's ArchConfig for a configuration file: its registry entry
    with the file's "replace" applied (a list there is a tuple field), and
    every published key that the file maps to a config field checked equal."""
    from repro.configs import registry
    p = conf["program"]
    replace = {k: tuple(v) if isinstance(v, list) else v
               for k, v in p.get("replace", {}).items()}
    cfg = registry.get_config(p["arch"]).replace(**replace)
    for key, field in p["keys"].items():
        if getattr(cfg, field) != conf[key]:
            raise BenchError(f"{conf['name']}: {key}={conf[key]!r} but the "
                             f"program runs {field}={getattr(cfg, field)!r}")
    return cfg


def make_weights(shapes, spec: dict, key, shardings=None):
    """Every leaf of the program's parameter tree drawn from `key` in one
    jitted call, on the device, in the leaf's own dtype.  `spec` maps a
    leaf's name to (rank of the unstacked leaf, its fan-in axes or None for
    a norm scale); leading axes beyond that rank are layer stacks."""
    import math
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            rank, fan = spec[path[-1].key]
            x = jax.random.normal(jax.random.fold_in(key, i), sds.shape, sds.dtype)
            if fan is None:
                std = 0.1
            else:
                lead = len(sds.shape) - rank
                std = 1.0 / math.sqrt(math.prod(sds.shape[lead + a] for a in fan))
            leaves.append(x * jnp.asarray(std, sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(key)
