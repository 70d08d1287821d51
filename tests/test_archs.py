"""Per-arch smoke tests (deliverable f): each assigned architecture's
REDUCED config runs forward / train_step / prefill / decode on CPU with
correct shapes, no NaNs, and prefill+decode == full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.configs import registry
from repro.models import lm, steps
from repro.optim import adamw

ARCHS = registry.list_archs()


def _smoke(arch):
    cfg = registry.get_smoke_config(arch)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512
    assert cfg.n_experts <= 4
    return cfg


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = _smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    batch = make_batch(cfg, B, S)
    logits, aux, _ = lm.forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_nothing_nan(arch):
    cfg = _smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init_opt_state(params)
    batch = make_batch(cfg, 2, 32)
    params, opt, m = steps.train_step(params, opt, batch, cfg=cfg)
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))
    assert int(opt["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    cfg = _smoke(arch)
    if cfg.n_experts:   # capacity drops differ between prefill and decode
        cfg = cfg.replace(capacity_factor=8.0)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 33
    full = make_batch(cfg, B, S, labels=False)
    logits_full, _, _ = lm.forward(params, cfg, full)
    pre = {k: (v[:, :S - 1] if k == "tokens"
               else (v[:, :, :S - 1] if k == "mrope_positions" else v))
           for k, v in full.items()}
    _, cache = steps.prefill(params, pre, cfg=cfg, cache_len=S + 4)
    pos = jnp.full((B,), S - 1, jnp.int32)
    if cfg.use_mrope:
        pos = jnp.broadcast_to(pos[:, None], (B, 3))
    got, _ = lm.decode_step(params, cfg, full["tokens"][:, S - 1:S], pos, cache)
    want = logits_full[:, -1]
    rel = float(jnp.max(jnp.abs(got - want))) / (float(jnp.max(jnp.abs(want))) + 1e-9)
    assert rel < 2e-3, f"{arch}: prefill+decode diverges from forward ({rel})"


@pytest.mark.parametrize("arch", ["gemma3_4b", "h2o_danube_3_4b", "zamba2_1_2b",
                                  "xlstm_1_3b"])
def test_multi_step_decode_stays_finite(arch):
    cfg = _smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S, n_gen = 2, 16, 12
    batch = make_batch(cfg, B, S, labels=False)
    last, cache = steps.prefill(params, batch, cfg=cfg, cache_len=S + n_gen + 1)
    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    start = jnp.full((B,), S, jnp.int32)
    toks, _ = steps.greedy_decode_loop(params, cache, tok, start, n_gen, cfg=cfg)
    assert toks.shape == (B, n_gen)
    assert (np.asarray(toks) >= 0).all() and (np.asarray(toks) < cfg.vocab_size).all()


def test_full_configs_match_assignment_sheet():
    spec = {
        "granite_moe_3b_a800m": dict(n_layers=32, d_model=1536, n_heads=24,
                                     n_kv_heads=8, vocab_size=49155,
                                     n_experts=40, top_k=8, moe_d_ff=512),
        "xlstm_1_3b": dict(n_layers=48, d_model=2048, n_heads=4, vocab_size=50304),
        "granite_3_8b": dict(n_layers=40, d_model=4096, n_heads=32,
                             n_kv_heads=8, d_ff=12800, vocab_size=49155),
        "gemma3_4b": dict(n_layers=34, d_model=2560, n_heads=8, n_kv_heads=4,
                          d_ff=10240, vocab_size=262144),
        "deepseek_v2_lite_16b": dict(n_layers=27, d_model=2048, n_heads=16,
                                     vocab_size=102400, n_experts=64, top_k=6,
                                     moe_d_ff=1408, kv_lora_rank=512),
        "h2o_danube_3_4b": dict(n_layers=24, d_model=3840, n_heads=32,
                                n_kv_heads=8, d_ff=10240, vocab_size=32000),
        "whisper_base": dict(n_layers=6, d_model=512, n_heads=8, d_ff=2048,
                             vocab_size=51865),
        "minitron_4b": dict(n_layers=32, d_model=3072, n_heads=24,
                            n_kv_heads=8, d_ff=9216, vocab_size=256000),
        "qwen2_vl_7b": dict(n_layers=28, d_model=3584, n_heads=28,
                            n_kv_heads=4, d_ff=18944, vocab_size=152064),
        "zamba2_1_2b": dict(n_layers=38, d_model=2048, n_heads=32,
                            n_kv_heads=32, d_ff=8192, vocab_size=32000,
                            ssm_state=64),
        "zamba2_7b": dict(n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
                          head_dim=224, d_ff=14336, vocab_size=32000,
                          ssm_state=64, ssm_heads=112, ssm_groups=2,
                          n_shared_blocks=2, adapter_rank=128),
    }
    for arch, fields in spec.items():
        cfg = registry.get_config(arch)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, f"{arch}.{k}: {getattr(cfg, k)} != {v}"
        assert cfg.citation


def test_long_context_skip_policy():
    """DESIGN.md skip matrix: who runs long_500k."""
    runs = {a: registry.runnable(registry.get_config(a),
                                 registry.INPUT_SHAPES["long_500k"])[0]
            for a in ARCHS}
    assert runs == {
        "granite_moe_3b_a800m": False, "xlstm_1_3b": True, "granite_3_8b": False,
        "gemma3_4b": True, "deepseek_v2_lite_16b": False, "h2o_danube_3_4b": True,
        "whisper_base": False, "minitron_4b": False, "qwen2_vl_7b": False,
        "zamba2_1_2b": True, "zamba2_7b": True,
    }

