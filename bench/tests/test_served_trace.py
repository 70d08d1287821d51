"""served_trace.served_decode_hlo: the batcher's own decode program,
checked against a smoke-size batcher traced on the CPU."""
import re

import jax
import pytest

from bench import program_trace, served_trace
from bench.tests.test_program_trace import _serve


@pytest.fixture(scope="module")
def model():
    from repro.configs import registry
    from repro.models import lm
    cfg = registry.get_smoke_config("h2o_danube_3_4b")
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def test_the_traced_decode_ops_are_the_served_program_s_and_scoped(model):
    cfg, _ = model
    _, events, _, _ = _serve(model, record=True)
    hlo = served_trace.served_decode_hlo(cfg, 2, 64)
    instructions = set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", hlo, re.M))
    ops = {program_trace.instruction(n) for evs in events["devices"].values()
           for n, _, _, m in evs if m == program_trace.DECODE_MODULE}
    assert ops and ops <= instructions, sorted(ops - instructions)[:10]
    scopes = program_trace.reduce(events, program_trace.hlo_op_names(hlo))["scopes"]
    assert scopes.get("attention", 0) > 0 and scopes.get("ffn", 0) > 0, scopes
