"""zamba2_7b: the configuration file against the program, the counts of
work against the program's own parameter tree, the plain reference
against a token-by-token float64 recurrence written here, and what
decides `correct` in its cell at smoke size (bench/conftest.py)."""
import argparse
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.conftest import ZAMBA2_SMOKE_LOGIT_GAP
from bench.tests import smoke
from bench.tests.test_bench_cells import measure, root  # noqa: F401
from repro.configs import registry
from repro.models import blocks, lm

CONF = harness.load_json(smoke.BENCH / "configs" / "zamba2_7b.json")
MODEL = harness.load_module(smoke.BENCH / "configs" / "zamba2_7b.py")
CELL = "zamba2_7b.batch_decode"


def test_the_file_maps_onto_the_program():
    cfg = harness.program_config(CONF)
    assert cfg.hybrid_layer_ids == tuple(CONF["hybrid_layer_ids"]) == (6, 11, 17, 23)
    assert [i for i, k in enumerate(CONF["layers_block_type"]) if k == "hybrid"] \
        == list(cfg.hybrid_layer_ids)
    assert len(CONF["layers_block_type"]) == cfg.n_layers == 27
    assert cfg.attn_scale == (CONF["attention_head_dim"] / 2) ** -0.5
    assert cfg.mlp_act == "geglu" and cfg.tie_embeddings and not cfg.use_kernels
    # the cut keeps the published layers up to 26 and their invocations
    full = registry.get_config(CONF["program"]["arch"])
    assert full.n_layers == CONF["published"]["num_hidden_layers"] == 81
    assert list(full.hybrid_layer_ids) == CONF["published"]["hybrid_layer_ids"]
    assert cfg.hybrid_layer_ids == tuple(i for i in full.hybrid_layer_ids if i < 27)
    # stage 1 invokes blocks 0, 1, 0, 1
    plan = blocks.build_plan(cfg)
    used = [(p.first_invocation + g) % cfg.n_shared_blocks
            for p in plan if p.hybrid for g in range(p.n_groups)]
    assert used == [0, 1, 0, 1]


@pytest.mark.parametrize("conf", [CONF, {**CONF, "num_hidden_layers": 81,
                                         **CONF["published"]}])
def test_the_reference_reads_the_program_s_layout(conf):
    cfg = registry.get_config("zamba2_7b").replace(
        n_layers=conf["num_hidden_layers"],
        hybrid_layer_ids=tuple(conf["hybrid_layer_ids"]))
    assert MODEL._plan(conf) == [(p.n_groups, len(p.kinds), p.hybrid,
                                  p.first_invocation) for p in blocks.build_plan(cfg)]


def test_param_count_and_least_decode_bytes_match_the_program():
    cfg = harness.program_config(CONF)
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert MODEL.param_count(CONF) == n == 2_968_362_608       # 2.968 B
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names <= set(MODEL.WEIGHTS)
    # weights that multiply a token, each shared block once an invocation
    small = sum(x.size for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]
                if p[-1].key in ("norm1", "norm2", "final_norm", "conv_b",
                                 "dt_bias", "A_log", "D_skip", "ssm_norm"))
    shared = sum(x.size for x in jax.tree_util.tree_leaves(shapes["shared"]))
    shared_norms = sum(x.size for p, x in jax.tree_util.tree_flatten_with_path(
        shapes["shared"])[0] if p[-1].key.startswith("norm"))
    mm = MODEL.matmul_params(CONF)
    assert mm == n - small + (shared - shared_norms)
    # one step at contexts 10 and 20: weights, embedding rows, state in and
    # out of every layer, and the K/V rows of 4 invocations
    state = 27 * (112 * 64 * 64 + 3 * (7168 + 2 * 2 * 64)) * 4
    row = 4 * 2 * 32 * 224 * 2
    assert MODEL.decode_min_bytes(CONF, [10, 20]) == \
        2 * (mm + 2 * 3584) + 2 * 2 * state + (11 + 21) * row
    flops = MODEL.flops_per_token(CONF, 99)
    assert flops == 2 * mm + 4 * 4 * 32 * 224 * 100 + 4 * 27 * 112 * 64 * 64


# -- the reference against a token-by-token float64 recurrence --------------

SMALL = {"num_hidden_layers": 5, "hybrid_layer_ids": [1, 2, 4], "hidden_size": 16,
         "attention_hidden_size": 32, "intermediate_size": 24,
         "num_attention_heads": 2, "num_key_value_heads": 2,
         "attention_head_dim": 8, "n_mamba_heads": 4, "mamba_headdim": 8,
         "mamba_d_state": 4, "adapter_rank": 3, "vocab_size": 64,
         "chunk_size": 8}


def _small():
    conf = {**CONF, **SMALL}
    cfg = registry.get_config("zamba2_7b").replace(
        n_layers=5, hybrid_layer_ids=(1, 2, 4), d_model=16, n_heads=2,
        n_kv_heads=2, head_dim=8, attn_scale=4 ** -0.5, d_ff=24,
        vocab_size=64, ssm_state=4, ssm_heads=4, ssm_chunk=8, adapter_rank=3,
        param_dtype="float32", dtype="float32")
    return conf, cfg


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * (1 + scale)


def _f64_logits(w, conf, tokens):
    """One sequence, one token at a time: the layer equations with the SSM
    state and conv window carried per layer and K/V kept per invocation."""
    w = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), w)
    eps, theta, nb = conf["rms_norm_eps"], conf["rope_theta"], conf["num_mem_blocks"]
    H, P, G, N = (conf[k] for k in ("n_mamba_heads", "mamba_headdim",
                                    "mamba_ngroups", "mamba_d_state"))
    inner, hd = H * P, conf["attention_head_dim"]
    layers = []                        # (mamba params, (block, invocation) or None)
    for p, (groups, n, hybrid, first) in enumerate(MODEL._plan(conf)):
        for g in range(groups):
            gw = jax.tree_util.tree_map(lambda a: a[g], w[f"phase{p}"])
            for j in range(n):
                inv = None
                if hybrid and j == 0:
                    inv = (jax.tree_util.tree_map(lambda a: a[(first + g) % nb],
                                                  w["shared"]), gw["hybrid"])
                layers.append((gw[f"slot{j}"], inv))
    hs = [np.zeros((H, P, N)) for _ in layers]
    convs = [np.zeros((conf["mamba_d_conv"] - 1, inner + 2 * G * N)) for _ in layers]
    kv = [([], []) for _ in layers]
    half = hd // 2
    freqs = theta ** (-np.arange(half) / half)

    def rope(v, t):
        c, s = np.cos(t * freqs), np.sin(t * freqs)
        return np.concatenate([v[..., :half] * c - v[..., half:] * s,
                               v[..., half:] * c + v[..., :half] * s], -1)

    gelu = np.vectorize(lambda v: 0.5 * v * (1 + math.erf(v / math.sqrt(2))))
    out = []
    for t, tok in enumerate(tokens):
        x = e = w["embed"][tok]
        for li, (lw, inv) in enumerate(layers):
            add = 0.0
            if inv is not None:
                sw, iw = inv
                s = _rms(np.concatenate([x, e]), sw["norm1"], eps)
                a = sw["mixer"]
                q = rope(np.einsum("d,dhk->hk", s, a["wq"]), t)
                kv[li][0].append(rope(np.einsum("d,dhk->hk", s, a["wk"]), t))
                kv[li][1].append(np.einsum("d,dhk->hk", s, a["wv"]))
                K, V = np.stack(kv[li][0]), np.stack(kv[li][1])     # (t+1,H,hd)
                sc = np.einsum("hk,thk->ht", q, K) * (hd / 2) ** -0.5
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                pr /= pr.sum(-1, keepdims=True)
                o = np.einsum("ht,thk->hk", pr, V)
                hh = _rms(np.einsum("hk,hkd->d", o, a["wo"]), sw["norm2"], eps)
                lo = hh @ iw["adapter_in"]
                g_ = hh @ sw["ffn"]["w_gate"] + lo @ iw["adapter_gate"]
                u_ = hh @ sw["ffn"]["w_up"] + lo @ iw["adapter_up"]
                add = (gelu(g_) * u_) @ sw["ffn"]["w_down"] @ iw["linear"]
            m = lw["mixer"]
            u = _rms(x + add, lw["norm1"], eps)
            z, xbc = u @ m["in_proj"], u @ m["xbc_proj"]
            dt = np.log1p(np.exp(u @ m["dt_proj"] + m["dt_bias"]))
            win = np.concatenate([convs[li], xbc[None]])
            convs[li] = win[1:]
            c = (win * m["conv_w"]).sum(0) + m["conv_b"]
            c = c / (1 + np.exp(-c))
            xs = c[:inner].reshape(H, P)
            Bm, Cm = c[inner:inner + G * N].reshape(G, N), c[inner + G * N:].reshape(G, N)
            A = -np.exp(m["A_log"])
            y = np.zeros((H, P))
            for h in range(H):
                g = h // (H // G)
                hs[li][h] = np.exp(dt[h] * A[h]) * hs[li][h] + dt[h] * np.outer(xs[h], Bm[g])
                y[h] = hs[li][h] @ Cm[g] + m["D_skip"][h] * xs[h]
            y = y.reshape(inner) * z / (1 + np.exp(-z))
            y = _rms(y.reshape(G, -1), m["ssm_norm"].reshape(G, -1), eps).reshape(inner)
            x = x + y @ m["out_proj"]
        out.append(_rms(x, w["final_norm"], eps) @ w["embed"].T)
    return np.stack(out)


def test_the_reference_matches_a_float64_recurrence():
    """16 positions, two SSD blocks: the reference's blocked quadratic form
    and carried state, its attention and shared-block wiring, against the
    float64 recurrence.  The reference is float32 at HIGHEST: it reads
    2.8e-6 and 5.3e-6 off on seeds 11 and 13, against logits of size ~2.5;
    2e-5 allows for f32 rounding over 5 layers and 16 positions."""
    conf, cfg = _small()
    w = lm.init_params(jax.random.PRNGKey(11), cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(12), (16,), 0, 64))
    got = np.asarray(MODEL.forward(w, conf, jnp.asarray(toks)[None]))[0]
    want = _f64_logits(w, conf, toks)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# -- what decides `correct` in the cell, at smoke size on the CPU -----------

@pytest.mark.parametrize("seed", [11, 2 ** 32 + 12])
def test_fp8_control_fails_where_the_program_passes(root, seed):  # noqa: F811
    cell = harness.Cell(CELL, root)
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    counter = harness.CompileCounter()
    try:
        rec = cell.driver.run(cell, args, jax.devices()[:1], 0.0, counter,
                              control=True)
    finally:
        counter.close()
    assert rec["checks"]["logit_gap"]["value"] <= ZAMBA2_SMOKE_LOGIT_GAP \
        < rec["control_gap"]


def _state_unchanged(real):
    """Decode that returns the cache it was given: the SSM and conv state
    of every layer never moves past the prompt."""
    def step(params, cfg, tokens, positions, cache):
        logits, _ = real(params, cfg, tokens, positions, cache)
        return logits, cache
    return step


def _token_altered(real):
    def step(params, cfg, tokens, positions, cache):
        logits, new = real(params, cfg, tokens, positions, cache)
        return jnp.roll(logits, 1, axis=-1), new
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_a_broken_decode_step_is_not_correct(root, monkeypatch, fault):  # noqa: F811
    monkeypatch.setattr(lm, "decode_step", fault(lm.decode_step))
    out = measure(root, CELL, seed=21)
    assert out["result"]["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
