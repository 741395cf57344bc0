"""CASCADE FP4 matmul — Pallas TPU kernel.

TPU-native adaptation of the paper's CASCADE array (Sections 10, 13):

* Weights live in HBM as **packed FP4 E2M1** (two codes per uint8, packed
  along the contraction dim) — this is the memory-roofline win the paper's
  HBM-balance analysis (Table 10) depends on (4 bits/weight).
* Each grid step stages an FP4 weight tile into VMEM, decodes it
  arithmetically (no gathers — integer shifts and masks, VPU friendly)
  and feeds the MXU in bf16 with an fp32 VMEM scratch accumulator.
  The HBM->VMEM double-buffered pipeline is the TPU analogue of the paper's
  HILT staging hierarchy; the K-grid accumulation revisits are the analogue
  of the CASCADE inter-array partial-sum latches: partial sums never leave
  the chip (grid dims are ("parallel", "parallel", "arbitrary")).
* Per-(K-group, column) scales are applied at the accumulation epilogue, and
  the bias is added at the column output — mirroring the paper's
  "biases are added in the output sums HILT" (Section 2.2).

Two numeric modes:

* **fast** (:func:`cascade_matmul_pallas`, the TPU production path). The
  decode is bound by the vector unit, not by HBM, so it is built to spend
  few vector ops per weight. A packed byte holds an even K row (low nibble)
  and the next odd K row (high nibble), so the low-nibble tile is
  contracted with the even-K activations and the high-nibble tile with the
  odd-K ones: two dots into one f32 accumulator, and the decoded tile is
  never interleaved back into K order. The activations come in split and
  transposed, ``(2, K/2, M)``, so that K lies on sublanes and a K block
  needs no lane alignment (K = 13440 has K/2 = 6720, which no multiple of
  128 divides). Each nibble decodes to twice its E2M1 value, an integer
  looked up in a 32-bit constant by a shift (:func:`_twice_fp4`); the 0.5
  is folded into the group-scale multiply of the (bm, bn) product, an
  exact power of two.
* **exact** (:func:`cascade_matmul_exact_pallas`, interpret mode only) —
  decode to f32, scale the *weight tile* elementwise, cast to
  ``compute_dtype`` and dot, on one block. This performs literally the
  same dequantize -> dot -> bias operations as the jnp serving path
  (``quant.dequantize_weight`` + ``jnp.dot``), so interpret-mode results
  are bit-identical to it — the token-exactness contract of the fused
  serving path (``ServeConfig.fused``). The scales input is pre-expanded to
  per-row ``(K, N)`` so arbitrary group sizes broadcast exactly like the
  jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant

# 2 * |E2M1 value| of the magnitude codes 0..7 ({0, 1, 2, 3, 4, 6, 8, 12}),
# four bits each, code c at bits 4c..4c+3 — as a signed int32 constant
_TWICE_E2M1 = int(np.array(sum(int(2 * v) << 4 * c for c, v in enumerate(quant.FP4_VALUES)),
                           np.uint32).view(np.int32))
_SIGN_BIT = -(1 << 31)


def _decode_fp4_block(codes: jax.Array, dtype) -> jax.Array:
    """Arithmetic FP4 E2M1 decode (no table gather)."""
    c = codes.astype(jnp.int32)
    s = (c >> 3) & 1
    e = (c >> 1) & 3
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, (1.0 + 0.5 * m) * jnp.exp2(e.astype(jnp.float32) - 1.0))
    return jnp.where(s == 1, -mag, mag).astype(dtype)


def _twice_fp4(p: jax.Array, high: bool) -> jax.Array:
    """Twice the E2M1 values of the low (``high=False``) or high nibbles of
    packed bytes ``p`` (int32, sign-extended from int8), as f32.

    The magnitude code's 4-bit entry is shifted out of ``_TWICE_E2M1``
    (an integer, so ±0 needs no special case) and converted once; the
    code's sign bit is moved to the f32 sign bit."""
    if high:
        shift = (p >> 2) & 28           # 4 * bits 4..6
        sign = p & _SIGN_BIT            # bit 7, sign-extended to bit 31
    else:
        shift = (p << 2) & 28           # 4 * bits 0..2
        sign = (p << 28) & _SIGN_BIT    # bit 3
    mag = (jnp.int32(_TWICE_E2M1) >> shift) & 15
    bits = lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32) | sign
    return lax.bitcast_convert_type(bits, jnp.float32)


def _kernel(x_ref, wq_ref, s_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # widen the bytes as int8, so that they are sign-extended and need no
    # mask (Mosaic has no vector shift on 8-bit lanes)
    p = wq_ref[...].astype(jnp.int8).astype(jnp.int32)   # (bk//2, bn)
    even = _twice_fp4(p, high=False).astype(jnp.bfloat16)  # K rows 0, 2, ...
    odd = _twice_fp4(p, high=True).astype(jnp.bfloat16)    # K rows 1, 3, ...
    k_rows = (((0,), (0,)), ((), ()))   # contract (bk//2, bm) with (bk//2, bn)
    prod = lax.dot_general(x_ref[0], even, k_rows, preferred_element_type=jnp.float32)
    prod += lax.dot_general(x_ref[1], odd, k_rows, preferred_element_type=jnp.float32)
    # the scale is constant across the K-block (group_size % bk == 0); the
    # decode gave twice each weight, so the product is scaled by scale / 2
    acc_ref[...] += prod * (0.5 * s_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] + b_ref[...]).astype(o_ref.dtype)


def cascade_matmul_pallas(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    bias: jax.Array,
    *,
    block_m: int,
    block_n: int,
    block_k: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K); packed: (K//2, N) uint8; scales: (G, N) f32 with
    group_size = K // G and group_size % block_k == 0; bias: (1, N) f32.
    Returns (M, N) out_dtype, with x in bf16 on the MXU.

    The activations are split into even and odd K rows and transposed once
    here, ``(2, K//2, M)``. The scales are laid out as (G, 1, N), so each
    K-block reads one (1, block_n) row that meets the (8, 128) block rule
    for any G."""
    m, kdim = x.shape
    n = packed.shape[1]
    assert packed.shape[0] * 2 == kdim
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0, (
        f"unpadded dims ({m},{n},{kdim}) vs blocks ({block_m},{block_n},{block_k})")
    group_size = kdim // scales.shape[0]
    assert group_size % block_k == 0, (
        f"group_size {group_size} must be a multiple of block_k {block_k}")
    # the barrier keeps x in its (M, K) layout before the split: without it
    # XLA merges the split into the producer's reshape (the decode
    # attention's (B*H, D) output ahead of the o-projection) and lays the
    # (M, K/2, 2) intermediate out with a minor dim of 2, 64x the bytes
    x = lax.optimization_barrier(x.astype(jnp.bfloat16))
    xt = x.reshape(m, kdim // 2, 2).transpose(2, 1, 0)
    scales = scales.reshape(scales.shape[0], 1, n)
    nk = kdim // block_k
    bk2 = block_k // 2
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // block_m, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((2, bk2, block_m), lambda i, j, k: (0, k, i)),
            pl.BlockSpec((bk2, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((None, 1, block_n),
                         lambda i, j, k, gs=group_size, bk=block_k: (k * bk // gs, 0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="cascade_matmul",
    )(xt, packed, scales, bias)


def _exact_kernel(x_ref, wq_ref, s_ref, b_ref, o_ref, *, compute_dtype, has_bias: bool):
    # widen before shifting: Mosaic has no vector shift on 8-bit lanes
    packed = wq_ref[...].astype(jnp.int32)  # (K//2, N) byte values
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    k2, n = packed.shape
    codes = jnp.stack([lo, hi], axis=1).reshape(k2 * 2, n)
    # per-row (K, N) scales: the same elementwise dequant multiply as
    # quant.dequantize_weight, then the dot in compute_dtype — bit-identical
    # to the jnp serving path
    w = (_decode_fp4_block(codes, jnp.float32) * s_ref[...]).astype(compute_dtype)
    x = x_ref[...].astype(compute_dtype)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if has_bias:
        acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def cascade_matmul_exact_pallas(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    bias: jax.Array,
    *,
    out_dtype=jnp.float32,
    has_bias: bool = True,
) -> jax.Array:
    """Interpret-mode parity kernel on one block. x: (M, K); packed:
    (K//2, N) uint8; scales: per-row (K, N) f32 (pre-expanded by the
    caller); bias: (1, N) f32. Dequantizes the whole tile like
    ``quant.dequantize_weight`` and dots in ``out_dtype``."""
    m, kdim = x.shape
    n = packed.shape[1]
    assert packed.shape[0] * 2 == kdim
    assert scales.shape == (kdim, n), (
        f"exact mode needs per-row scales (K, N); got {scales.shape}")
    return pl.pallas_call(
        functools.partial(_exact_kernel, compute_dtype=out_dtype, has_bias=has_bias),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=True,
        name="cascade_matmul",
    )(x, packed, scales, bias)
