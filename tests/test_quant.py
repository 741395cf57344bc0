"""FP4/FP5/FP8 numerics — unit + property tests for repro.core.quant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import quant

jax.config.update("jax_platform_name", "cpu")


def test_fp4_codec_roundtrip_all_codes():
    codes = jnp.arange(16, dtype=jnp.uint8)
    vals = quant.fp4_decode(codes)
    np.testing.assert_array_equal(
        np.abs(np.asarray(vals)), np.concatenate([quant.FP4_VALUES, quant.FP4_VALUES]))
    assert bool(jnp.all(quant.fp4_encode(vals) == codes))


def test_fp4_encode_matches_native_cast():
    ml_dtypes = pytest.importorskip("ml_dtypes")
    x = np.linspace(-8, 8, 1001, dtype=np.float32)
    ours = quant.fp4_decode(quant.fp4_encode(jnp.asarray(x)))
    native = x.astype(ml_dtypes.float4_e2m1fn).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ours), native)


def test_pack_unpack_identity():
    c = jnp.arange(64, dtype=jnp.uint8).reshape(16, 4) % 16
    for axis in (0,):
        assert bool(jnp.all(quant.unpack_fp4(quant.pack_fp4(c, axis), axis) == c))


def test_fp5_product_exhaustive():
    """All 256 FP4xFP4 products: exact except both-mantissa-1.1 cases, which
    truncate 10.01b -> 10b (paper Section 10.6)."""
    vals = quant.fp4_decode(jnp.arange(16, dtype=jnp.uint8))
    a = jnp.repeat(vals, 16)
    b = jnp.tile(vals, 16)
    p = np.asarray(quant.fp5_e3m1_product(a, b))
    exact = np.asarray(a * b)
    # mantissa of |exact| has >1 bit only for 1.5*1.5-type products
    both_wide = (np.abs(np.asarray(a)) % np.exp2(np.floor(np.log2(np.maximum(np.abs(a), 1e-9)))) != 0) & \
                (np.abs(np.asarray(b)) % np.exp2(np.floor(np.log2(np.maximum(np.abs(b), 1e-9)))) != 0)
    # where not both-wide, product must be exact
    np.testing.assert_array_equal(p[~both_wide & (exact != 0)], exact[~both_wide & (exact != 0)])
    # truncation is always toward zero and within one ulp
    assert np.all(np.abs(p) <= np.abs(exact))
    nz = exact != 0
    assert np.all(np.abs(p[nz] - exact[nz]) <= np.abs(exact[nz]) * 0.25 + 1e-9)


def test_fp5_range_covers_all_products_without_saturation():
    vals = quant.FP4_VALUES[1:]  # nonzero magnitudes
    prods = np.outer(vals, vals)
    assert prods.max() == 36.0 and prods.min() == 0.25
    # E3M1 bias-2: normal range [2^-2, 1.5*2^5]; 36 truncates to 32 (exp 5)
    assert float(quant.fp5_e3m1_product(jnp.float32(6.0), jnp.float32(6.0))) == 32.0


def test_fp8_truncate_properties():
    x = jnp.array([500.0, -500.0, 448.0, 1.0625, 2.0 ** -10, -0.9999, 0.0])
    y = np.asarray(quant.fp8_e4m3_truncate(x))
    assert y[0] == 448.0 and y[1] == -448.0       # saturation
    assert y[2] == 448.0
    assert y[3] == 1.0                             # truncation toward zero
    assert y[4] == 0.0                             # below subnormal step
    assert abs(y[5]) <= 0.9999                     # magnitude never grows
    assert y[6] == 0.0


@settings(max_examples=200, deadline=None)
@given(st.floats(min_value=-1000, max_value=1000, allow_nan=False))
def test_fp8_truncate_idempotent_and_monotone_magnitude(v):
    x = jnp.float32(v)
    y = quant.fp8_e4m3_truncate(x)
    y2 = quant.fp8_e4m3_truncate(y)
    assert float(y) == float(y2)                   # idempotent
    assert abs(float(y)) <= min(abs(v), 448.0) + 1e-6  # truncation toward zero


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.sampled_from([16, 64, 128]), st.sampled_from([8, 16, 32]),
       st.sampled_from([0, 16]))
def test_ptq_roundtrip_error_bound(seed, k, n, group):
    """Group-absmax FP4 PTQ: |w - deq(q(w))| <= group_absmax / 6.
    The widest FP4 gap is 4 -> 6 (= 2 raw, scaled by absmax/6); RNE error is
    at most half that gap = absmax/6."""
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (k, n)) * 0.05
    packed, scales = quant.quantize_weight(w, group_size=group)
    wd = quant.dequantize_weight(packed, scales, jnp.float32)
    g = group if group else k
    wg = np.asarray(w).reshape(k // g, g, n)
    absmax = np.abs(wg).max(axis=1, keepdims=True)
    err = np.abs(np.asarray(wd).reshape(k // g, g, n) - wg)
    assert np.all(err <= absmax / 6.0 + 1e-7)


def test_cascade_exact_oracle_representable_case():
    """When every partial sum is exactly representable in FP8 E4M3, the
    CASCADE column accumulation must be bit-exact vs f32: 16 adds of 0.5
    (steps stay within the 3-bit mantissa at every exponent <= 3)."""
    x4 = jnp.ones((2, 16))
    w4 = jnp.full((16, 3), 0.5)
    exact = np.asarray(quant.cascade_matmul_exact(x4, w4))
    np.testing.assert_array_equal(exact, np.full((2, 3), 8.0))


def test_cascade_exact_oracle_well_scaled_statistics():
    """For well-scaled inputs (the regime the paper's FP8 accumulators are
    designed for — Section 10.4 picks FP8-over-INT8 for dynamic range), the
    truncating accumulation tracks f32 within a bounded relative Frobenius
    error, and saturates at +/-448."""
    key = jax.random.PRNGKey(0)
    x4 = quant.fp4_decode(quant.fp4_encode(jax.random.normal(key, (8, 64)) * 0.4))
    w4 = quant.fp4_decode(quant.fp4_encode(jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.4))
    exact = np.asarray(quant.cascade_matmul_exact(x4, w4))
    ref = np.asarray(x4 @ w4)
    rel_fro = np.linalg.norm(exact - ref) / (np.linalg.norm(ref) + 1e-9)
    assert rel_fro < 0.25, f"FP8 accumulation drift too large: {rel_fro}"
    assert np.all(np.abs(exact) <= 448.0)          # saturation respected


def test_cascade_exact_bias_preload():
    """Biases preloaded into the output-sum HILT (paper Section 13.1)."""
    x4 = jnp.ones((2, 4))
    w4 = jnp.ones((4, 3))
    bias = jnp.array([1.0, -1.0, 0.5])
    out = quant.cascade_matmul_exact(x4, w4, bias=jnp.broadcast_to(bias, (2, 3)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(4.0 + bias)[None].repeat(2, 0))


def test_fake_quant_fp4_ste_gradient():
    w = jnp.array([[0.3, -0.7], [1.2, 0.01]])
    g = jax.grad(lambda w: jnp.sum(quant.fake_quant_fp4(w) ** 2))(w)
    # STE: gradient flows as if identity: d/dw sum(fq(w)^2) ~= 2*fq(w)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(quant.fake_quant_fp4(w)), rtol=1e-5)


def test_fake_quant_fp4_forward_is_quantized():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 16))
    fq = quant.fake_quant_fp4(w)
    packed, scales = quant.quantize_weight(w)
    wd = quant.dequantize_weight(packed, scales, jnp.float32)
    np.testing.assert_allclose(np.asarray(fq), np.asarray(wd), atol=1e-6)


def test_fp4_all_codes_full_chain_roundtrip():
    """All 16 codes survive the FULL serving chain bit-exactly:
    decode -> fp4_round fixed point -> encode -> pack -> unpack."""
    codes = jnp.arange(16, dtype=jnp.uint8).reshape(8, 2)
    vals = quant.fp4_decode(codes)
    # every representable value is a fixed point of the RNE rounder
    np.testing.assert_array_equal(np.asarray(quant.fp4_round(vals)),
                                  np.asarray(vals))
    re = quant.fp4_encode(vals)
    assert bool(jnp.all(re == codes))
    packed = quant.pack_fp4(re, 0)
    assert packed.dtype == jnp.uint8 and packed.shape == (4, 2)
    assert bool(jnp.all(quant.unpack_fp4(packed, 0) == codes))


def test_quantize_weight_odd_k_pads_zero_row():
    """Odd-K weights pack via one all-zero pad row; dequantize returns the
    padded (K+1)-row matrix whose extra row is exactly zero."""
    w = jax.random.normal(jax.random.PRNGKey(7), (33, 6)) * 0.3
    packed, scales = quant.quantize_weight(w)
    assert packed.shape == (17, 6)
    deq = quant.dequantize_weight(packed, scales, jnp.float32)
    assert deq.shape == (34, 6)
    np.testing.assert_array_equal(np.asarray(deq[-1]), np.zeros(6, np.float32))
    with pytest.raises(AssertionError):
        quant.quantize_weight(w, group_size=3)   # grouped scales need even K
