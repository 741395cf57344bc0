"""FP4/FP8 quantization core for the ZettaLith CASCADE reproduction.

Implements, faithfully to the paper (Sections 2.2, 10.1, 10.4, 10.6):

* FP4 E2M1 weight/activation codec (values +/-{0, .5, 1, 1.5, 2, 3, 4, 6}).
* FP5 E3M1 truncated products: the paper's multiplier truncates the 2-bit
  product mantissa 10.01b -> 10b ("the difference is minor"), i.e. mantissa
  round-toward-zero to one bit.
* FP8 E4M3 saturating, truncating accumulation (non-IEEE: no inf/nan path,
  saturates at +/-448, rounds toward zero) used to accumulate partial sums
  down a CASCADE column.
* Group-wise / per-column absmax post-training quantization (PTQ) of weight
  matrices into packed FP4 codes + scales.
* Quantization-aware-training (QAT) fake-quant with a straight-through
  estimator, as required by paper Section 4 for FP4 transformer deployment.

Everything here is pure jnp and serves as the numerical oracle for the Pallas
kernels in ``repro.kernels``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# --------------------------------------------------------------------------
# FP4 E2M1 codec
# --------------------------------------------------------------------------

#: Values of the 8 non-negative FP4 E2M1 codes (code = s<<3 | e<<1 | m).
FP4_VALUES = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], dtype=np.float32)
FP4_MAX = 6.0


def fp4_round(x: jax.Array) -> jax.Array:
    """Round f32 values onto the FP4 E2M1 value grid (RNE, saturating) via
    the native ``float4_e2m1fn`` cast, which lowers for CPU and TPU alike."""
    return x.astype(jnp.float4_e2m1fn).astype(jnp.float32)


def fp4_encode(x: jax.Array) -> jax.Array:
    """Encode float -> FP4 E2M1 code (uint8 in 0..15), round-to-nearest-even.

    Rounds onto the FP4 value grid and maps the value back to its code via
    the magnitude table.
    """
    v = fp4_round(x)
    sign = (v < 0) | ((v == 0) & (jnp.signbit(x.astype(jnp.float32))))
    mag = jnp.abs(v)
    # searchsorted over the 8 exact magnitudes
    code = jnp.searchsorted(jnp.asarray(FP4_VALUES), mag, side="left").astype(jnp.uint8)
    return jnp.where(sign, code + jnp.uint8(8), code).astype(jnp.uint8)


def fp4_decode(code: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Decode FP4 E2M1 code (uint8 0..15) -> float, arithmetically (no gather).

    TPU-friendly decode used inside kernels as well:
      sign = bit3; e = bits2..1; m = bit0
      value = (-1)^sign * (e == 0 ? 0.5*m : (1 + 0.5*m) * 2^(e-1))
    """
    code = code.astype(jnp.int32)
    s = (code >> 3) & 1
    e = (code >> 1) & 3
    m = code & 1
    mf = m.astype(jnp.float32)
    ef = e.astype(jnp.float32)
    normal = (1.0 + 0.5 * mf) * jnp.exp2(ef - 1.0)
    sub = 0.5 * mf
    mag = jnp.where(e == 0, sub, normal)
    val = jnp.where(s == 1, -mag, mag)
    return val.astype(dtype)


def pack_fp4(codes: jax.Array, axis: int = 0) -> jax.Array:
    """Pack pairs of 4-bit codes along ``axis`` into uint8 (low nibble first)."""
    codes = jnp.moveaxis(codes, axis, 0)
    assert codes.shape[0] % 2 == 0, "packing axis must be even"
    lo = codes[0::2].astype(jnp.uint8)
    hi = codes[1::2].astype(jnp.uint8)
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return jnp.moveaxis(packed, 0, axis)


def unpack_fp4(packed: jax.Array, axis: int = 0) -> jax.Array:
    """Inverse of :func:`pack_fp4`."""
    packed = jnp.moveaxis(packed, axis, 0)
    lo = packed & jnp.uint8(0xF)
    hi = (packed >> 4) & jnp.uint8(0xF)
    inter = jnp.stack([lo, hi], axis=1)  # (P, 2, ...)
    out = inter.reshape((packed.shape[0] * 2,) + packed.shape[1:])
    return jnp.moveaxis(out, 0, axis)


# --------------------------------------------------------------------------
# Mantissa truncation primitives (FP5 product / FP8 accumulate)
# --------------------------------------------------------------------------


def truncate_mantissa_f32(x: jax.Array, mbits: int) -> jax.Array:
    """Truncate (round toward zero) an f32 mantissa to ``mbits`` bits.

    Works on sign-magnitude IEEE754 layout so it is correct for negatives.
    """
    xi = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    mask = jnp.uint32(0xFFFFFFFF ^ ((1 << (23 - mbits)) - 1))
    return lax.bitcast_convert_type(xi & mask, jnp.float32)


def fp5_e3m1_product(a: jax.Array, b: jax.Array) -> jax.Array:
    """Paper Section 10.6 multiplier: FP4*FP4 with FP5 E3M1 truncated result.

    All 256 FP4xFP4 products have mantissas 1.0, 1.1 or 10.01 (binary); the
    multiplier truncates 10.01 -> 10 and renormalizes, i.e. a 1-bit mantissa
    truncation. With bias 2 the E3M1 exponent range [2^-2, 1.5*2^5] covers
    every product magnitude (0.25 .. 36) so no saturation path is needed
    (verified exhaustively in tests).
    """
    p = a.astype(jnp.float32) * b.astype(jnp.float32)
    return truncate_mantissa_f32(p, 1)


FP8_E4M3_MAX = 448.0
_FP8_MIN_NORMAL = 2.0 ** -6
_FP8_SUB_STEP = 2.0 ** -9


def fp8_e4m3_truncate(x: jax.Array) -> jax.Array:
    """Paper Sections 10.4/10.6 accumulator numerics: FP8 E4M3, saturating,
    truncating (round toward zero), non-IEEE (no inf/nan propagation).
    """
    x = x.astype(jnp.float32)
    sat = jnp.clip(x, -FP8_E4M3_MAX, FP8_E4M3_MAX)
    normal = truncate_mantissa_f32(sat, 3)
    sub = jnp.trunc(sat / _FP8_SUB_STEP) * _FP8_SUB_STEP
    return jnp.where(jnp.abs(sat) < _FP8_MIN_NORMAL, sub, normal)


def cascade_column_accumulate(products: jax.Array, init: jax.Array | None = None) -> jax.Array:
    """Sequentially accumulate FP5 products down a CASCADE column in FP8.

    ``products``: (..., K) FP5-truncated products in f32 carrier.
    Returns (...,) FP8-valued column sums. ``init`` models the bias preloaded
    into the output-sum HILT (paper Section 13.1).
    """
    k = products.shape[-1]
    acc0 = jnp.zeros(products.shape[:-1], jnp.float32) if init is None else init.astype(jnp.float32)

    def body(i, acc):
        return fp8_e4m3_truncate(acc + products[..., i])

    return lax.fori_loop(0, k, body, acc0)


def cascade_matmul_exact(x4: jax.Array, w4: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    """Bit-accurate CASCADE matmul oracle.

    x4: (..., K) FP4-valued activations (f32 carrier, already FP4-rounded)
    w4: (K, N) FP4-valued weights
    Computes FP5-truncated products and FP8 sequential column accumulation —
    exactly the dataflow of paper Table 6. O(K*N) memory; test-scale only.
    """
    prods = fp5_e3m1_product(x4[..., :, None], w4[None, ...] if w4.ndim == 2 else w4)
    # prods: (..., K, N) -> accumulate over K sequentially per column
    prods = jnp.moveaxis(prods, -2, -1)  # (..., N, K)
    init = None
    if bias is not None:
        init = jnp.broadcast_to(fp8_e4m3_truncate(bias), prods.shape[:-1])
    return cascade_column_accumulate(prods, init)


# --------------------------------------------------------------------------
# PTQ: absmax group quantization of weight matrices
# --------------------------------------------------------------------------


def quantize_weight(
    w: jax.Array, group_size: int = 0
) -> Tuple[jax.Array, jax.Array]:
    """Quantize a (K, N) weight matrix to packed FP4 codes + scales.

    group_size: contraction-dim group for scales; 0 => one scale per output
    column (a single group spanning all of K). Scales are chosen so the group
    absmax maps to FP4_MAX (=6.0).

    Odd K (pad-to-pack): packing needs an even contraction dim, so an odd-K
    matrix is padded with one all-zero row first (zeros encode to code 0 and
    decode to exactly 0, so the pad contributes nothing to any product).
    Consumers pad the activations with a matching zero column — see
    ``kernels.ops.cascade_matmul`` / ``kernels.ref.cascade_matmul_ref``.
    Only supported with per-column scales (group_size=0): the pad row joins
    the single group without changing its absmax.

    Returns:
      packed: (ceil(K/2), N) uint8, two K-adjacent codes per byte (low
              nibble = even row)
      scales: (G, N) f32 with G = K//group_size (>= 1)
    """
    k, n = w.shape
    if k % 2:
        assert group_size == 0, "odd K needs per-column scales (group_size=0)"
        w = jnp.concatenate([w.astype(jnp.float32),
                             jnp.zeros((1, n), jnp.float32)], axis=0)
        k += 1
    g = group_size if group_size > 0 else k
    assert k % g == 0, f"K={k} not divisible by group_size={g}"
    wg = w.reshape(k // g, g, n).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wg), axis=1)  # (G, N)
    scales = jnp.where(absmax > 0, absmax / FP4_MAX, 1.0)
    q = wg / scales[:, None, :]
    codes = fp4_encode(q).reshape(k, n)
    return pack_fp4(codes, axis=0), scales


def dequantize_weight(packed: jax.Array, scales: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_weight` -> (K, N) dense weights.

    For an odd-K original the returned matrix keeps the zero pad row
    (K+1 rows) — the codes alone cannot tell padded from real zeros."""
    codes = unpack_fp4(packed, axis=0)
    k, n = codes.shape
    g = k // scales.shape[0]
    vals = fp4_decode(codes, jnp.float32).reshape(k // g, g, n)
    return (vals * scales[:, None, :]).reshape(k, n).astype(dtype)


# --------------------------------------------------------------------------
# QAT fake-quant (straight-through estimator) — paper Section 4
# --------------------------------------------------------------------------


def fake_quant_fp4(w: jax.Array, group_size: int = 0) -> jax.Array:
    """Differentiable FP4 fake-quant: forward = quantize->dequantize,
    backward = identity (STE). Used for QAT so trained weights survive FP4
    serving (paper Section 4: 'effectively trained in FP4 using QAT')."""
    orig_shape = w.shape
    w2 = w.reshape(-1, orig_shape[-1]) if w.ndim != 2 else w
    k = w2.shape[0]
    g = group_size if (group_size > 0 and k % group_size == 0) else k

    def qdq(w2):
        wg = w2.reshape(k // g, g, -1).astype(jnp.float32)
        absmax = jnp.max(jnp.abs(wg), axis=1)
        scales = jnp.where(absmax > 0, absmax / FP4_MAX, 1.0)
        q = wg / scales[:, None, :]
        v = fp4_round(q)
        return (v * scales[:, None, :]).reshape(w2.shape)

    out = w2 + lax.stop_gradient(qdq(w2) - w2.astype(jnp.float32)).astype(w2.dtype)
    return out.reshape(orig_shape)


def fake_quant_fp8_e4m3(x: jax.Array) -> jax.Array:
    """FP8 fake-quant with STE (used for KV-cache QAT experiments)."""
    q = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + lax.stop_gradient(q - x)
