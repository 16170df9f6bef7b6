"""Per-kernel allclose sweeps (interpret=True) against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st  # optional dev dep
from repro.kernels import ref
from repro.kernels.agg_reduce import agg_reduce, agg_reduce_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import (dequantize_int8, pack_int4, quantize_int4,
                                    quantize_int8, topk_sparsify, unpack_int4)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- agg_reduce
@pytest.mark.parametrize("C,N,dtype", [
    (1, 128, jnp.float32), (20, 5000, jnp.float32), (7, 333, jnp.float32),
    (20, 4096, jnp.bfloat16), (64, 10000, jnp.float32),
])
def test_agg_reduce_sweep(C, N, dtype):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (C, N), dtype)
    w = jax.random.uniform(ks[1], (C,)) * 50
    m = (jax.random.uniform(ks[2], (C,)) > 0.4).astype(jnp.float32)
    got = agg_reduce(x, w, m, interpret=True)
    want = ref.agg_reduce_ref(x, w, m)
    # fp32 summation-order tolerance scales with Σ|w|·|x|
    tol = 1e-3 if dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=tol)


@settings(max_examples=20, deadline=None)
@given(C=st.integers(1, 16), N=st.integers(1, 700), seed=st.integers(0, 2**30))
def test_agg_reduce_property(C, N, seed):
    """kernel == Σ_c w_c m_c x_c against a float64 numpy oracle."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(C, N)).astype(np.float32)
    w = rng.uniform(0, 10, C).astype(np.float32)
    m = (rng.random(C) > 0.5).astype(np.float32)
    got = np.asarray(agg_reduce(jnp.asarray(x), jnp.asarray(w), jnp.asarray(m),
                                interpret=True))
    want = ((w * m)[:, None].astype(np.float64) * x.astype(np.float64)).sum(0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(C=st.integers(1, 48), N=st.integers(1, 4000),
       dtype_name=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**30))
def test_agg_reduce_random_shapes_dtypes_vs_ref(C, N, dtype_name, seed):
    """Randomized client counts × parameter sizes × dtypes against
    kernels.ref (the fixed-shape sweep above can't catch a padding or
    tiling bug that only bites at odd N or large C)."""
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (C, N), dtype)
    w = jax.random.uniform(ks[1], (C,)) * 50
    m = (jax.random.uniform(ks[2], (C,)) > 0.4).astype(jnp.float32)
    got = agg_reduce(x, w, m, interpret=True)
    want = ref.agg_reduce_ref(x, w, m)
    tol = 1e-3 if dtype == jnp.float32 else 0.3 * max(1, C // 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=tol)


@settings(max_examples=10, deadline=None)
@given(N=st.integers(1, 12000), scale_exp=st.integers(-6, 6),
       dtype_name=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**30))
def test_quantize_random_shapes_dtypes_vs_ref(N, scale_exp, dtype_name,
                                              seed):
    """Randomized lengths × magnitudes × dtypes: the Pallas quantizer is
    bit-identical to the jnp reference (same noise stream), and the
    dequantized roundtrip stays within one quantization step."""
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    key = jax.random.PRNGKey(seed)
    x = (jax.random.normal(key, (N,), jnp.float32)
         * (10.0 ** scale_exp)).astype(dtype)
    q, s = quantize_int8(x, key, interpret=True)
    qr, sr = ref.quantize_int8_ref(x, key)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    assert float(s) == float(sr)
    xd = dequantize_int8(q, s, interpret=True)
    xdr = ref.dequantize_int8_ref(qr, sr)
    np.testing.assert_array_equal(np.asarray(xd), np.asarray(xdr))
    err = np.max(np.abs(np.asarray(xd) - np.asarray(x, np.float32)))
    assert err <= float(s) * 1.01


# ------------------------------------------------------------------ quantize
@pytest.mark.parametrize("N", [128, 8191, 8192, 100_001])
def test_quantize_roundtrip(N):
    x = jax.random.normal(KEY, (N,), jnp.float32)
    q, s = quantize_int8(x, KEY, interpret=True)
    qr, sr = ref.quantize_int8_ref(x, KEY)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    assert np.isclose(float(s), float(sr))
    xd = dequantize_int8(q, s, interpret=True)
    assert float(jnp.max(jnp.abs(xd - x))) <= float(s) * 1.01


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_quantize_unbiased(seed):
    """stochastic rounding: E[dequant(quant(x))] == x."""
    key = jax.random.PRNGKey(seed)
    x = jnp.linspace(-1.0, 1.0, 257)
    errs = []
    for i in range(16):
        k = jax.random.fold_in(key, i)
        q, s = quantize_int8(x, k, interpret=True)
        errs.append(np.asarray(dequantize_int8(q, s, interpret=True) - x))
    mean_err = np.mean(errs)
    assert abs(mean_err) < 2e-3


@settings(max_examples=10, deadline=None)
@given(N=st.integers(1, 12000), scale_exp=st.integers(-6, 6),
       seed=st.integers(0, 2**30))
def test_quantize_int4_random_vs_ref(N, scale_exp, seed):
    """int4 path: bit-identical to the jnp reference, values in [-7, 7],
    and the nibble pack/unpack wire roundtrip is lossless."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (N,), jnp.float32) * (10.0 ** scale_exp)
    q, s = quantize_int4(x, key, interpret=True)
    qr, sr = ref.quantize_int4_ref(x, key)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    assert float(s) == float(sr)
    assert int(np.abs(np.asarray(q)).max()) <= 7
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(pack_int4(q), N)), np.asarray(q))
    err = np.max(np.abs(np.asarray(dequantize_int8(q, s, interpret=True))
                        - np.asarray(x)))
    assert err <= float(s) * 1.01


@settings(max_examples=10, deadline=None)
@given(N=st.integers(1, 5000), frac=st.floats(0.001, 1.0),
       seed=st.integers(0, 2**30))
def test_topk_sparsify_random_vs_ref(N, frac, seed):
    """top-k threshold mask: bit-identical to the jnp reference; keeps
    at least k entries (ties at the threshold all kept), zeroes the rest."""
    import math
    x = jax.random.normal(jax.random.PRNGKey(seed), (N,), jnp.float32)
    k = max(1, min(N, math.ceil(frac * N)))
    got = topk_sparsify(x, k, interpret=True)
    want = ref.topk_sparsify_ref(x, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    nz = int(np.count_nonzero(np.asarray(got)))
    assert nz >= min(k, int(np.count_nonzero(np.asarray(x))))
    kept = np.abs(np.asarray(got))[np.asarray(got) != 0]
    dropped = np.abs(np.asarray(x))[np.asarray(got) == 0]
    if kept.size and dropped.size:
        assert kept.min() >= dropped.max()


@settings(max_examples=10, deadline=None)
@given(C=st.integers(1, 24), N=st.integers(1, 4000),
       bits=st.sampled_from([4, 8]), seed=st.integers(0, 2**30))
def test_agg_reduce_quant_fused_vs_unfused_ref(C, N, bits, seed):
    """The fused aggregate+quantize kernel matches the unfused oracle
    (einsum reduce, then quantize) within one quantization level — the
    per-block summation order can move a value across a rounding
    boundary, so bit-exactness is deliberately not the contract."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (C, N), jnp.float32)
    w = jax.random.uniform(ks[1], (C,)) * 10
    m = (jax.random.uniform(ks[2], (C,)) > 0.3).astype(jnp.float32)
    q, s = agg_reduce_quant(x, w, m, key, bits=bits, interpret=True)
    qr, sr = ref.agg_reduce_quant_ref(x, w, m, key, bits)
    assert np.isclose(float(s), float(sr), rtol=1e-5)
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1


def test_quantize_topk_zero_length_guards():
    """N=0 / C=0 are reachable (an ONU whose every client crashed
    mid-round) — every entry point returns empty instead of erroring."""
    e = jnp.zeros((0,), jnp.float32)
    for fn in (quantize_int8, quantize_int4):
        q, s = fn(e, KEY, interpret=True)
        assert q.shape == (0,) and float(s) == 1.0
    assert dequantize_int8(jnp.zeros((0,), jnp.int8), jnp.float32(1.0),
                           interpret=True).shape == (0,)
    assert topk_sparsify(e, 5, interpret=True).shape == (0,)
    assert pack_int4(jnp.zeros((0,), jnp.int8)).shape == (0,)
    assert unpack_int4(jnp.zeros((0,), jnp.uint8), 0).shape == (0,)
    assert agg_reduce(jnp.zeros((0, 7)), jnp.zeros((0,)), jnp.zeros((0,)),
                      interpret=True).shape == (7,)
    for shape in ((0, 7), (3, 0)):
        q, s = agg_reduce_quant(jnp.zeros(shape), jnp.zeros((shape[0],)),
                                jnp.zeros((shape[0],)), KEY, interpret=True)
        assert q.shape == (shape[1],) and float(s) == 1.0


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,H,KV,S,hd,win,dtype", [
    (2, 4, 2, 512, 64, 0, jnp.float32),
    (1, 4, 1, 512, 128, 0, jnp.float32),      # MQA
    (2, 2, 2, 256, 64, 128, jnp.float32),     # sliding window
    (1, 8, 4, 512, 256, 0, jnp.float32),      # RG-size head_dim
    (1, 4, 4, 256, 64, 0, jnp.bfloat16),      # MHA bf16
])
def test_flash_attention_sweep(B, H, KV, S, hd, win, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    got = flash_attention(q, k, v, causal=True, window=win, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 0.03
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """the kernel and the model's chunked-jnp attention agree."""
    from repro.models.layers import causal_attention
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      head_dim=16, q_chunk=64)
    ks = jax.random.split(KEY, 3)
    B, S = 2, 256
    q = jax.random.normal(ks[0], (B, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 16), jnp.float32)
    from repro.common.sharding import ShardingRules
    rules = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
    model_out = causal_attention(q, k, v, cfg, rules)
    kern_out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), causal=True,
                               bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(model_out),
                               np.asarray(kern_out.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- rglru
@pytest.mark.parametrize("B,S,C", [(1, 64, 128), (2, 512, 640), (3, 256, 896)])
def test_rglru_scan_sweep(B, S, C):
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, C)))
    b = jax.random.normal(ks[1], (B, S, C))
    h0 = jax.random.normal(ks[2], (B, C))
    got_o, got_h = rglru_scan(a, b, h0, interpret=True)
    want_o, want_h = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)


def test_rglru_kernel_matches_model_scan():
    """associative_scan (model) == sequential ref == kernel."""
    from repro.models.rglru import rglru_scan as assoc_scan
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 128, 256)))
    b = jax.random.normal(ks[1], (2, 128, 256))
    m = assoc_scan(a, b)
    r, _ = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(m), np.asarray(r), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (1, 2, 128, 32, 64), (2, 3, 256, 64, 64), (1, 1, 64, 16, 16),
])
def test_rwkv6_scan_sweep(B, H, S, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, hd)) * 0.5)
    u = jax.random.normal(ks[4], (H, hd)) * 0.5
    got_o, got_s = rwkv6_scan(r, k, v, logw, u, chunk=chunk, interpret=True)
    want_o, want_s = ref.rwkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=2e-3, atol=2e-3)


def test_rwkv6_model_chunked_matches_ref():
    """the model's chunked jnp form equals the exact sequential recurrence."""
    from repro.models.rwkv6 import _chunk_body
    B, H, S, hd, W = 1, 2, 128, 32, 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, hd)) * 0.5)
    u = jax.random.normal(ks[4], (H, hd)) * 0.5
    # run the model chunk body over chunks (inputs laid out (B, W, H, hd))
    S_c = jnp.zeros((B, H, hd, hd))
    outs = []
    for i in range(S // W):
        sl = slice(i * W, (i + 1) * W)
        o, S_c = _chunk_body(r[:, :, sl].transpose(0, 2, 1, 3),
                             k[:, :, sl].transpose(0, 2, 1, 3),
                             v[:, :, sl].transpose(0, 2, 1, 3),
                             logw[:, :, sl].transpose(0, 2, 1, 3),
                             u, S_c, None)
        outs.append(o.transpose(0, 2, 1, 3))
    got = jnp.concatenate(outs, axis=2)
    want_o, want_s = ref.rwkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_o),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(S_c), np.asarray(want_s),
                               rtol=2e-3, atol=2e-3)
