"""zamba2_7b at smoke size in `smoke.make_root`'s tree, so that the
parametrised cell tests run it on the CPU in seconds.  `bench/tests/smoke.py`
shrinks only h2o; this wraps its `make_root` until the shrink moves there."""
import json

import pytest

from bench.tests import smoke

# Smoke-size limit of zamba2_7b's logit gap: sound runs read 0.04 to 0.80
# over 13 seeds on the CPU (0-9, 11, 2**32 + 12, 2**33 + 5), the fp8
# control 2.26 to 3.78 (see PERF.md section 6); 1.3 lies between with room
# on both sides.
ZAMBA2_SMOKE_LOGIT_GAP = 1.3
ZAMBA2_SMOKE = {
    "num_hidden_layers": 6, "hybrid_layer_ids": [1, 2, 4],
    "layers_block_type": ["mamba", "hybrid", "hybrid", "mamba", "hybrid", "mamba"],
    "hidden_size": 64, "attention_hidden_size": 128, "intermediate_size": 96,
    "ffn_hidden_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_query_groups": 4, "attention_head_dim": 32, "n_mamba_heads": 8,
    "mamba_headdim": 16, "mamba_d_state": 16, "adapter_rank": 8,
    "vocab_size": 512, "chunk_size": 16,
}
ZAMBA2_PROGRAM = {"n_layers": 6, "hybrid_layer_ids": [1, 2, 4], "d_model": 64,
                  "d_ff": 96, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
                  "attn_scale": 16 ** -0.5, "ssm_heads": 8, "ssm_state": 16,
                  "adapter_rank": 8, "vocab_size": 512, "ssm_chunk": 16}


def shrink_zamba2(root, logit_gap=ZAMBA2_SMOKE_LOGIT_GAP):
    """zamba2_7b.json in a smoke tree at smoke size, with its smoke limit."""
    path = root / "bench" / "configs" / "zamba2_7b.json"
    conf = json.loads(path.read_text())
    conf.update(ZAMBA2_SMOKE)
    conf["program"]["replace"].update(ZAMBA2_PROGRAM)
    conf["serving"] = {"max_slots": 2, "max_len": 64, "prefill_chunk": 16}
    conf["correct"]["logit_gap"] = logit_gap
    path.write_text(json.dumps(conf))
    return root


@pytest.fixture(autouse=True, scope="session")
def _zamba2_at_smoke_size():
    make_root = smoke.make_root

    def make_root_with_zamba2(tmp, **kw):
        return shrink_zamba2(make_root(tmp, **kw))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(smoke, "make_root", make_root_with_zamba2)
        yield
