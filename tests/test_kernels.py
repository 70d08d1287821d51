"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssm_scan

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("shape", [(4, 64), (3, 17, 128), (2, 5, 7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    scale = (jax.random.normal(jax.random.PRNGKey(1), shape[-1:]) * 0.1).astype(dtype)
    got = rmsnorm(x, scale, block_rows=8)
    want = ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("sq,skv,hq,hkv,d,window", [
    (64, 64, 4, 4, 32, 0),        # MHA causal
    (100, 100, 4, 2, 32, 0),      # GQA, non-divisible seq
    (64, 64, 8, 2, 64, 24),       # sliding window
    (33, 128, 4, 4, 32, 0),       # cross-length (q_offset prefill tail)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(sq, skv, hq, hkv, d, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (2, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (2, skv, hkv, d), dtype)
    off = skv - sq
    got = flash_attention(q, k, v, causal=True, window=window, q_offset=off,
                          block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("s,hq,hkv,d,block_k", [
    (128, 4, 4, 32, 64), (200, 8, 2, 64, 64), (64, 4, 1, 128, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(s, hq, hkv, d, block_k, dtype):
    ks = jax.random.split(KEY, 3)
    B = 3
    q = jax.random.normal(ks[0], (B, hq, d), dtype)
    kc = jax.random.normal(ks[1], (B, s, hkv, d), dtype)
    vc = jax.random.normal(ks[2], (B, s, hkv, d), dtype)
    lens = jnp.array([s, s // 2, 1], jnp.int32)
    got = decode_attention(q, kc, vc, lens, block_k=block_k)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


GQA_HEADS = [(32, 8), (8, 8), (8, 1), (28, 4)]     # (Hq, Hkv): GQA, MHA, MQA, 7:1


def _repeat_attend(q, k, v, valid):
    """Float32 oracle in the repeat form: every KV head copied to its G
    query heads, then plain masked attention.  q (B,Q,Hq,D), k/v
    (B,K,Hkv,D), valid (B,Q,K) -> (B,Q,Hq,D)."""
    q, k, v = (np.asarray(t, np.float32) for t in (q, k, v))
    g = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    logits = np.where(np.asarray(valid)[:, None], logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", w, v)


@pytest.mark.parametrize("hq,hkv", GQA_HEADS)
@pytest.mark.parametrize("fill", ["one", "mid", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_ref_matches_repeat_oracle(hq, hkv, fill, dtype):
    """The grouped contraction equals attending over a repeated cache, and
    rows at or past cache_len leave the output bit-for-bit unchanged."""
    b, s, d = 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    n = {"one": 1, "mid": s // 2 + 3, "full": s}[fill]
    lens = jnp.array([n, max(1, n - 7)], jnp.int32)
    got = ref.decode_attention_ref(q, kc, vc, lens)
    valid = np.arange(s)[None, None, :] < np.asarray(lens)[:, None, None]
    want = _repeat_attend(q[:, None], kc, vc, valid)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **tol(dtype))
    dead = np.arange(s)[None, :, None, None] >= np.asarray(lens)[:, None, None, None]
    pert = ref.decode_attention_ref(q, jnp.where(dead, 555.0, kc).astype(dtype),
                                    jnp.where(dead, -555.0, vc).astype(dtype), lens)
    np.testing.assert_array_equal(np.asarray(pert, np.float32),
                                  np.asarray(got, np.float32))


@pytest.mark.parametrize("hq,hkv", GQA_HEADS)
@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_prefill_matches_repeat_oracle(hq, hkv, window, dtype):
    """Both branches of gqa_prefill (global cache, ring of `window` slots)
    against the repeat form over the whole written history: the query at
    position t sees every earlier position, or those in (t - window, t]."""
    from repro.configs import registry
    from repro.models import attention
    d_model, hd, c, s = 64, 16, 5, 32
    start = 13 if window else 5                  # the ring has wrapped
    cfg = registry.get_smoke_config("h2o_danube_3_4b").replace(
        d_model=d_model, n_heads=hq, n_kv_heads=hkv, head_dim=hd)
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv + window), 4)
    p = attention.gqa_init(ks[0], cfg, jnp.float32)
    x = jax.random.normal(ks[1], (1, c, d_model))
    hist_k = jax.random.normal(ks[2], (1, start, hkv, hd), dtype)
    hist_v = jax.random.normal(ks[3], (1, start, hkv, hd), dtype)
    size = window or s
    held = np.full(size, -1)
    for t in range(start):                       # what decode left in each slot
        held[t % size] = t
    rows = np.clip(held, 0, None)
    cache = {"k": jnp.where((held >= 0)[None, :, None, None], hist_k[:, rows], 0),
             "v": jnp.where((held >= 0)[None, :, None, None], hist_v[:, rows], 0)}
    pos = jnp.arange(start, start + c, dtype=jnp.int32)[None]
    got, _ = attention.gqa_prefill(p, x, cache, pos, cfg, window=window)

    q = attention.apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), pos,
                             cfg.rope_theta).astype(dtype)
    k = attention.apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), pos,
                             cfg.rope_theta).astype(dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"]).astype(dtype)
    keys = jnp.concatenate([hist_k, k], axis=1)  # position i in row i
    vals = jnp.concatenate([hist_v, v], axis=1)
    tq, kp = np.asarray(pos)[:, :, None], np.arange(start + c)[None, None, :]
    valid = (kp <= tq) & ((kp > tq - window) if window else True)
    o = _repeat_attend(q, keys, vals, valid)
    want = np.einsum("bshk,hkd->bsd", o, np.asarray(p["wo"], np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **tol(dtype))


@pytest.mark.parametrize("s,h,p,n,chunk", [
    (64, 2, 8, 16, 16), (96, 3, 16, 8, 32), (50, 1, 4, 4, 16),
])
def test_ssm_scan_kernel_matches_sequential_oracle(s, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    B = 2
    x = jax.random.normal(ks[0], (B, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    A = -jnp.abs(jax.random.normal(ks[2], (h,))) * 4
    Bm = jax.random.normal(ks[3], (B, s, n)) * 0.3
    Cm = jax.random.normal(ks[4], (B, s, n)) * 0.3
    y_ref, h_ref = ref.ssm_scan_ref(x, dt, A, Bm, Cm)
    y_k, h_k = ssm_scan(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y_k, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_k, h_ref, rtol=2e-4, atol=2e-4)
    # chunked-jnp twin agrees too (the default model path)
    y_j, h_j = ops.ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y_j, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_j, h_ref, rtol=2e-4, atol=2e-4)


def test_ssm_scan_extreme_decay_no_nan():
    """The masked-exponent regression: strong decay must not overflow."""
    ks = jax.random.split(KEY, 5)
    B, s, h, p, n = 1, 32, 4, 8, 16
    x = jax.random.normal(ks[0], (B, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)) + 2)
    A = -jnp.linspace(1.0, 16.0, h)
    Bm = jax.random.normal(ks[3], (B, s, n))
    Cm = jax.random.normal(ks[4], (B, s, n))
    for fn in (lambda: ssm_scan(x, dt, A, Bm, Cm, chunk=16)[0],
               lambda: ops.ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=16)[0]):
        assert not np.isnan(np.asarray(fn())).any()


def test_ops_ssm_scan_routes_only_what_the_kernel_takes():
    """ops.ssm_scan: the kernel for one group from a zero state; a carried
    state goes to the chunked jnp path; two groups under the kernel raise."""
    ks = jax.random.split(KEY, 6)
    B, s, h, p, n = 1, 32, 4, 8, 16
    x = jax.random.normal(ks[0], (B, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    A = -jnp.abs(jax.random.normal(ks[2], (h,))) * 4
    Bm = jax.random.normal(ks[3], (B, s, 1, n)) * 0.3
    Cm = jax.random.normal(ks[4], (B, s, 1, n)) * 0.3
    h0 = jax.random.normal(ks[5], (B, h, p, n)) * 0.3
    y_k, _ = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=16, use_kernel=True)
    y_j, _ = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(y_k, y_j, rtol=2e-4, atol=2e-4)
    y_kh, _ = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=16, h0=h0, use_kernel=True)
    y_jh, _ = ops.ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=16, h0=h0)
    np.testing.assert_array_equal(y_kh, y_jh)
    two = jnp.concatenate([Bm, Bm], axis=2)
    with pytest.raises(ValueError, match="one B/C group"):
        ops.ssm_scan(x, dt, A, two, two, chunk=16, use_kernel=True)


@pytest.mark.parametrize("sq,skv,hq,hkv,window,off", [
    (64, 64, 4, 2, 0, 0), (100, 100, 8, 2, 24, 0), (33, 128, 4, 4, 0, 95),
])
def test_flash_chunked_jnp_matches_ref(sq, skv, hq, hkv, window, off):
    """The 'fused attention' jnp twin (perf-variant model path)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, hq, 32))
    k = jax.random.normal(ks[1], (2, skv, hkv, 32))
    v = jax.random.normal(ks[2], (2, skv, hkv, 32))
    got = ops.flash_chunked_jnp(q, k, v, causal=True, window=window,
                                q_offset=off, chunk_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                   q_offset=off)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_ops_dispatch_kernel_vs_ref_paths_agree():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    a = ops.flash_attention(q, k, v, use_kernel=True, block_q=32, block_k=32)
    b = ops.flash_attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("s,h,d,chunk", [(37, 3, 8, 8), (64, 2, 16, 16),
                                         (50, 1, 32, 37)])
def test_mlstm_pallas_kernel_matches_sequential_oracle(s, h, d, chunk):
    from repro.kernels.mlstm_scan import mlstm_scan
    ks = jax.random.split(KEY, 5)
    B = 2
    q = jax.random.normal(ks[0], (B, s, h, d))
    k = jax.random.normal(ks[1], (B, s, h, d))
    v = jax.random.normal(ks[2], (B, s, h, d))
    logi = jax.random.normal(ks[3], (B, s, h)) * 0.5
    fpre = jax.random.normal(ks[4], (B, s, h)) + 2.0
    want = ref.mlstm_scan_ref(q, k, v, logi, fpre)
    got = mlstm_scan(q, k, v, logi, jax.nn.log_sigmoid(fpre), chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_mlstm_forward_kernel_dispatch_matches_jnp():
    """cfg.use_kernels routes the mLSTM block through the Pallas kernel."""
    from repro.configs import registry
    from repro.models import ssm
    cfg = registry.get_smoke_config("xlstm_1_3b")
    p = ssm.mlstm_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model)) * 0.5
    a = ssm.mlstm_forward(p, x, cfg)
    b = ssm.mlstm_forward(p, x, cfg.replace(use_kernels=True))
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
