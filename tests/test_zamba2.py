"""Zamba2 (family "hybrid") against its plain float32 reference
(bench/configs/zamba2_7b.py) at a small size on the CPU: the full forward,
chunked prefill then decode through ContinuousBatcher with a slot reused,
the batched Mamba2 chunk prefill against token-by-token decode, and the
assignment of shared blocks and adapters to invocations.

Weights come from the program's own initialisation: A_log and dt_bias
follow Mamba2's published one (A in [1, 16], dt in [1e-3, 1e-1]), so the
state carries over hundreds of positions and a prompt's early tokens still
move its last logits."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import lm, ssm
from repro.serving.continuous import ContinuousBatcher

REF_PATH = Path(__file__).resolve().parents[1] / "bench" / "configs" / "zamba2_7b.py"

# Program and reference both compute in float32 here and differ only in
# the order of some sums (chunked prefill and one-token decode against the
# reference's full forward).  Readings on the CPU: 0 for the full forward
# on seeds 0-3, at most 5.5e-6 through the batcher, against logits of size
# ~4.  1e-3 leaves room for other CPUs; the program in bfloat16 would miss
# it by an order of magnitude, and a swapped shared block or adapter moves
# logits by more than 2 (test_a_wrong_block_or_adapter_is_caught).
ATOL = 1e-3


def _reference():
    spec = importlib.util.spec_from_file_location("zamba2_7b_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def small_config(**kw):
    """zamba2_7b's structure at a small size: 6 layers with invocations
    before layers 1, 2 and 4 (shared blocks 0, 1, 0), two B/C groups of
    four heads, adapters of rank 8, f32."""
    cfg = registry.get_config("zamba2_7b").replace(
        n_layers=6, hybrid_layer_ids=(1, 2, 4), d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=32, attn_scale=16 ** -0.5, d_ff=96,
        vocab_size=256, ssm_state=16, ssm_heads=8, ssm_chunk=16,
        adapter_rank=8, param_dtype="float32", dtype="float32")
    return cfg.replace(**kw)


def ref_conf(cfg):
    """The reference's configuration keys (the published config's names)."""
    return {
        "hidden_size": cfg.d_model, "mamba_expand": cfg.d_inner_mult,
        "n_mamba_heads": cfg.ssm_heads, "mamba_headdim": cfg.ssm_head_dim,
        "mamba_ngroups": cfg.ssm_groups, "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.ssm_conv, "attention_hidden_size": cfg.shared_in,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "attention_head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
        "adapter_rank": cfg.adapter_rank, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "hybrid_layer_ids": list(cfg.hybrid_layer_ids),
        "num_mem_blocks": cfg.n_shared_blocks, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "chunk_size": cfg.ssm_chunk,
    }


def ref_logits(params, cfg, tokens):
    """Reference logits of each row of `tokens`, padded at the end to a
    whole number of SSD blocks (causal: the padding reads nothing back)."""
    toks = np.asarray(tokens, np.int32)
    s = toks.shape[1]
    pad = -s % cfg.ssm_chunk
    toks = np.pad(toks, ((0, 0), (0, pad)))
    with jax.default_matmul_precision("highest"):
        out = REF.forward(params, ref_conf(cfg), jnp.asarray(toks))
    return np.asarray(out)[:, :s]


@pytest.fixture(scope="module")
def model():
    cfg = small_config()
    return cfg, lm.init_params(jax.random.PRNGKey(3), cfg)


def test_init_follows_mamba2_published_ranges(model):
    cfg, params = model
    m = params["phase1"]["slot0"]["mixer"]
    A = np.exp(np.asarray(m["A_log"]))
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert A.min() >= 1.0 and A.max() <= 16.0
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 1e-1 * (1 + 1e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_the_reference(model, seed):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(seed), (2, 48), 0, cfg.vocab_size)
    got, _, _ = lm.forward(params, cfg, {"tokens": toks})
    want = ref_logits(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=ATOL)


def _swap_blocks(params):
    return dict(params, shared=jax.tree_util.tree_map(lambda a: a[::-1],
                                                      params["shared"]))


def _shift_adapters(params):
    """Adapters of invocations 1 and 2 (phase 2's two groups) swapped."""
    p2 = params["phase2"]
    hyb = dict(p2["hybrid"], **{k: v[::-1] for k, v in p2["hybrid"].items()
                                if k.startswith("adapter")})
    return dict(params, phase2=dict(p2, hybrid=hyb))


@pytest.mark.parametrize("fault", [_swap_blocks, _shift_adapters])
def test_a_wrong_block_or_adapter_is_caught(model, fault):
    """The blocks alternate 0, 1, 0 and each invocation has its own
    adapter: the program run with the blocks swapped, or with two
    invocations' adapters exchanged, disagrees with the reference."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 32), 0, cfg.vocab_size)
    want = ref_logits(params, cfg, toks)
    got, _, _ = lm.forward(fault(params), cfg, {"tokens": toks})
    assert float(np.abs(np.asarray(got) - want).max()) > 100 * ATOL


def test_plan_alternates_blocks_and_opens_a_group_per_invocation(model):
    from repro.models import blocks
    cfg, _ = model
    plan = blocks.build_plan(cfg)
    assert [(p.n_groups, len(p.kinds), p.hybrid, p.first_invocation)
            for p in plan] == [(1, 1, False, 0), (1, 1, True, 0), (2, 2, True, 1)]
    assert [(p.n_groups, len(p.kinds), p.hybrid, p.first_invocation)
            for p in plan] == REF._plan(ref_conf(cfg))


class _Recorder:
    """Wraps a batcher's jitted program and keeps the logits it returns."""

    def __init__(self, fn, log, kind):
        self.fn, self.log, self.kind = fn, log, kind

    def __call__(self, *a):
        logits, cache = self.fn(*a)
        self.log.append((self.kind, np.asarray(logits)))
        return logits, cache


def test_batcher_chunked_prefill_decode_and_slot_reuse_match_reference(model):
    """Two requests through one slot: prompts longer than a prefill chunk,
    then decode.  The logits that chose every served token equal the
    reference's full forward over the request's prompt and outputs; the
    second request sees none of the first's state."""
    cfg, params = model
    chunk, prompts = 8, (([5, 17, 200, 31] * 5)[:19], [9, 250, 3, 77] * 6)
    cb = ContinuousBatcher(cfg, params, max_slots=1, max_len=64,
                           prefill_chunk=chunk)
    log = []
    cb._prefill = _Recorder(cb._prefill, log, "prefill")
    cb._decode = _Recorder(cb._decode, log, "decode")
    reqs = [cb.submit(prompts[0], 6), cb.submit(prompts[1], 5)]
    cb.run()
    for r in reqs:
        n_pre = -(-len(r.prompt) // chunk)
        pre, log = log[:n_pre], log[n_pre:]
        dec, log = log[:r.max_new - 1], log[r.max_new - 1:]
        assert [k for k, _ in pre] == ["prefill"] * n_pre
        assert [k for k, _ in dec] == ["decode"] * (r.max_new - 1)
        got = np.stack([pre[-1][1][0, -1]] + [lg[0] for _, lg in dec])
        want = ref_logits(params, cfg, [r.prompt + r.output])[0]
        want = want[len(r.prompt) - 1:len(r.prompt) - 1 + r.max_new]
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
        assert r.output == [int(i) for i in got.argmax(-1)]
    assert not log


def test_batched_mamba2_prefill_matches_token_by_token_decode():
    """Chunks of 7 and 33 tokens (an SSD chunk is 16) from a carried state
    against 40 one-token decode steps: outputs and the final SSM and conv
    state.  The same f32 arithmetic in another order (chunked SSD against
    the one-step recurrence): 6e-7 at most here on outputs of size ~3;
    1e-4 leaves room for other CPUs' exp and sums."""
    cfg = small_config()
    p = ssm.mamba2_init(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, cfg.d_model))
    zero = {k: jnp.zeros(v, jnp.float32)
            for k, v in ssm.mamba2_cache_shape(cfg, 2).items()}
    y1, st = ssm.mamba2_prefill(p, x[:, :7], zero, cfg)
    y2, st = ssm.mamba2_prefill(p, x[:, 7:], st, cfg)
    got = np.concatenate([y1, y2], axis=1)
    dec = jax.jit(lambda c, xt: ssm.mamba2_decode(p, xt, c, cfg))
    c, want = zero, []
    for t in range(40):
        y, c = dec(c, x[:, t:t + 1])
        want.append(np.asarray(y[:, 0]))
    np.testing.assert_allclose(got, np.stack(want, axis=1), rtol=0, atol=1e-4)
    for k in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(st[k]), np.asarray(c[k]),
                                   rtol=0, atol=1e-4, err_msg=k)


def test_published_config_plan():
    """The published 81 layers: 6 plain, then invocations at 6, 11, 17, ...,
    77 in groups of 5, 6 (eleven times) and 4 layers; 13 invocations."""
    from repro.models import blocks
    cfg = registry.get_config("zamba2_7b")
    plan = blocks.build_plan(cfg)
    assert [(p.n_groups, len(p.kinds), p.hybrid) for p in plan] == \
        [(6, 1, False), (1, 5, True), (11, 6, True), (1, 4, True)]
    assert sum(p.n_groups * len(p.kinds) for p in plan) == 81
    assert cfg.ssm_head_dim == 64 and cfg.shared_in == 7168
