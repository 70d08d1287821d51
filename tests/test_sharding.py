"""Sharding rules + a real multi-device lowering smoke test (8 fake CPU
devices in a subprocess so the main test process keeps 1 device)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.models import lm, sharding as msh, steps
from repro.models.sharding import abstract_mesh

MESH = abstract_mesh((4, 2), ("data", "model"))
MESH3 = abstract_mesh((2, 2, 2), ("pod", "data", "model"))


def test_param_rules_cover_every_leaf():
    """Every param leaf of every arch resolves to a legal PartitionSpec."""
    for arch in registry.list_archs():
        cfg = registry.get_smoke_config(arch)
        spec = steps.params_spec(cfg)
        pspecs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: msh.fit_pspec(
                tuple(leaf.shape),
                msh._resolve(msh.leaf_spec(path, leaf), MESH), MESH),
            spec)
        for leaf, ps in zip(jax.tree_util.tree_leaves(spec),
                            jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P))):
            for dim, entry in zip(leaf.shape, tuple(ps)):
                if entry is not None:
                    assert dim % msh._axis_size(MESH, entry) == 0, (arch, leaf.shape, ps)


def test_fit_pspec_relocates_to_divisible_dim():
    # 24 heads don't divide 16-way model axis; relocate to d_model dim
    mesh = abstract_mesh((16, 16), ("data", "model"))
    fitted = msh.fit_pspec((1536, 24, 64), P(None, "model", None), mesh)
    assert tuple(fitted) in ((("model",), None, None), ("model", None, None))


def test_fit_pspec_drops_when_nothing_fits():
    mesh = abstract_mesh((16, 16), ("data", "model"))
    fitted = msh.fit_pspec((7, 5), P("model", None), mesh)
    assert all(e is None for e in tuple(fitted) + (None,))


def test_logical_batch_axis_spans_pod_and_data():
    resolved = msh._resolve(("batch", None), MESH3)
    assert tuple(resolved)[0] == ("pod", "data")


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = msh.constrain(x, "batch", "model")
    assert (x == y).all()


def test_multidevice_lowering_subprocess():
    """End-to-end: 8 fake devices, (2,4) mesh, smoke arch train_step lowers,
    compiles, and cost analysis is extractable."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, functools
from repro.configs import registry
from repro.launch import shardings
from repro.launch.mesh import make_mesh
from repro.models import sharding as msh, steps
from repro.launch.roofline import collective_bytes, cost_dict, roofline

cfg = registry.get_smoke_config("granite_3_8b").replace(dtype="bfloat16")
mesh = make_mesh((2, 4), ("data", "model"))
param_spec = steps.params_spec(cfg)
param_sh = msh.param_shardings(param_spec, mesh)
opt_spec = steps.opt_state_spec(param_spec)
opt_sh = shardings.opt_shardings(opt_spec, param_spec, mesh)
bspec = steps.batch_spec(cfg, 8, 32, train=True)
batch_sh = shardings.batch_shardings(bspec, mesh)
with msh.use_mesh(mesh):
    fn = functools.partial(steps.train_step, cfg=cfg)
    lowered = jax.jit(fn, in_shardings=(param_sh, opt_sh, batch_sh),
                      out_shardings=(param_sh, opt_sh, None)).lower(
        param_spec, opt_spec, bspec)
    compiled = lowered.compile()
cost = cost_dict(compiled)
assert cost.get("flops", 0) > 0, cost
coll = collective_bytes(compiled.as_text())
assert coll["total_bytes"] > 0, coll   # data-parallel grad all-reduce must exist
terms = roofline(cost["flops"], cost.get("bytes accessed", 0.0),
                 coll["total_bytes"], 8)
assert terms.dominant in ("compute", "memory", "collective")
print("SUBPROCESS_OK", coll["per_kind_counts"])
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)) or ".")
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


def test_dp_profile_lowering_subprocess():
    """dp+zero1 profile (§Perf B1): params replicate, batch spans all axes,
    collectives shrink to gradient reductions."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, functools
from repro.configs import registry
from repro.launch import shardings
from repro.launch.mesh import make_mesh
from repro.models import sharding as msh, steps
from repro.launch.roofline import collective_bytes

cfg = registry.get_smoke_config("xlstm_1_3b").replace(
    dtype="bfloat16", sharding_profile="dp", zero1=True)
mesh = make_mesh((2, 4), ("data", "model"))
with msh.use_profile("dp"), msh.use_mesh(mesh):
    param_spec = steps.params_spec(cfg)
    param_sh = msh.param_shardings(param_spec, mesh)
    # dp: every param replicated
    assert all(s.spec == jax.sharding.PartitionSpec()
               or all(e is None for e in s.spec)
               for s in jax.tree_util.tree_leaves(param_sh)), "params not replicated"
    opt_spec = steps.opt_state_spec(param_spec)
    opt_sh = shardings.opt_shardings(opt_spec, param_spec, mesh, zero1=True)
    # zero1: at least one moment leaf sharded over data
    specs = [s.spec for s in jax.tree_util.tree_leaves(opt_sh["mu"])]
    assert any("data" in [a for e in sp if e for a in (e if isinstance(e, tuple) else (e,))]
               for sp in specs), "zero1 did not shard moments"
    bspec = steps.batch_spec(cfg, 8, 32, train=True)
    batch_sh = shardings.batch_shardings(bspec, mesh)
    # batch spans both axes in dp
    tok_spec = batch_sh["tokens"].spec
    assert tok_spec[0] == ("data", "model"), tok_spec
    fn = functools.partial(steps.train_step, cfg=cfg)
    compiled = jax.jit(fn, in_shardings=(param_sh, opt_sh, batch_sh),
                       out_shardings=(param_sh, opt_sh, None)).lower(
        param_spec, opt_spec, bspec).compile()
    coll = collective_bytes(compiled.as_text())
    assert coll["total_bytes"] > 0
print("DP_SUBPROCESS_OK")
"""
    import os
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert "DP_SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
