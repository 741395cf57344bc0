"""CASCADE FP4 matmul — Pallas TPU kernel.

TPU-native adaptation of the paper's CASCADE array (Sections 10, 13):

* Weights live in HBM as **packed FP4 E2M1** (two codes per uint8, packed
  along the contraction dim) — this is the memory-roofline win the paper's
  HBM-balance analysis (Table 10) depends on (4 bits/weight).
* Each grid step stages an FP4 weight tile into VMEM, decodes it
  arithmetically (no gathers — sign/exponent/mantissa bit math, VPU friendly)
  and feeds the MXU in bf16 with an fp32 VMEM scratch accumulator.
  The HBM->VMEM double-buffered pipeline is the TPU analogue of the paper's
  HILT staging hierarchy; the K-grid accumulation revisits are the analogue
  of the CASCADE inter-array partial-sum latches: partial sums never leave
  the chip (grid dims are ("parallel", "parallel", "arbitrary")).
* Per-(K-group, column) scales are applied at the accumulation epilogue, and
  the bias is added at the column output — mirroring the paper's
  "biases are added in the output sums HILT" (Section 2.2).

Block shapes default to (bm, bn, bk) = (128, 256, 512): MXU-aligned
(multiples of 128); VMEM footprint per step =
  x tile 128*512*2B + packed w tile 256*256*1B + decoded 512*256*2B
  + acc 128*256*4B ~= 0.58 MB  << 16 MB VMEM (room for double buffering).

Two numeric modes (``exact_dequant``):

* **fast** (default, the TPU production path) — decode FP4 to bf16, feed the
  MXU in bf16, apply the group scale to the (bm, bn) *product* once per
  K-block (cheaper than scaling the (bk, bn) weight tile).
* **exact** — decode to f32, scale the *weight tile* elementwise, cast to
  ``compute_dtype`` and dot. With a single-block grid this performs literally
  the same dequantize -> dot -> bias operations as the jnp serving path
  (``quant.dequantize_weight`` + ``jnp.dot``), so interpret-mode results are
  bit-identical to it — the token-exactness contract of the fused serving
  path (``ServeConfig.fused``). In exact mode the scales input is pre-
  expanded to per-row ``(K, N)`` so arbitrary group sizes broadcast exactly
  like the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_fp4_block(codes: jax.Array, dtype) -> jax.Array:
    """Arithmetic FP4 E2M1 decode (no table gather)."""
    c = codes.astype(jnp.int32)
    s = (c >> 3) & 1
    e = (c >> 1) & 3
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, (1.0 + 0.5 * m) * jnp.exp2(e.astype(jnp.float32) - 1.0))
    return jnp.where(s == 1, -mag, mag).astype(dtype)


def _kernel(x_ref, wq_ref, s_ref, b_ref, o_ref, acc_ref, *, nk: int, out_dtype,
            compute_dtype, exact_dequant: bool, has_bias: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # widen before shifting: Mosaic has no vector shift on 8-bit lanes
    packed = wq_ref[...].astype(jnp.int32)  # (bk//2, bn) byte values
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    bk2, bn = packed.shape
    codes = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)
    if exact_dequant:
        # per-row (bk, bn) scales: the same elementwise dequant multiply as
        # quant.dequantize_weight, then the dot in compute_dtype — with a
        # single-block grid this is bit-identical to the jnp serving path
        w = (_decode_fp4_block(codes, jnp.float32) * s_ref[...]).astype(compute_dtype)
        x = x_ref[...].astype(compute_dtype)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    else:
        w = _decode_fp4_block(codes, jnp.bfloat16)  # unscaled FP4 values
        x = x_ref[...].astype(jnp.bfloat16)
        prod = jnp.dot(x, w, preferred_element_type=jnp.float32)  # (bm, bn) fp32
        # scale is constant across the K-block (group_size % bk == 0), applied
        # to the (bm, bn) product: cheaper than scaling the (bk, bn) weight tile.
        acc_ref[...] += prod * s_ref[...].astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(out_dtype)


def cascade_matmul_pallas(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    bias: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    compute_dtype=jnp.bfloat16,
    exact_dequant: bool = False,
    has_bias: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) bf16/f32; packed: (K//2, N) uint8; bias: (1, N) f32.
    Returns (M, N) out_dtype.

    ``scales``: fast mode takes (G, N) f32 with group_size = K // G and
    group_size % block_k == 0 (laid out as (G, 1, N) so each K-block reads
    one (1, block_n) row that meets the (8, 128) block rule for any G); exact mode (``exact_dequant=True``) takes
    per-row (K, N) f32 (pre-expanded by the caller) so the weight tile is
    dequantized exactly like ``quant.dequantize_weight`` before the dot.
    ``has_bias=False`` skips the bias add entirely (matching the jnp path's
    conditional add bit-for-bit; ``bias`` is still passed as zeros to keep
    the call signature static)."""
    m, kdim = x.shape
    n = packed.shape[1]
    assert packed.shape[0] * 2 == kdim
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0, (
        f"unpadded dims ({m},{n},{kdim}) vs blocks ({block_m},{block_n},{block_k})")
    if exact_dequant:
        assert scales.shape == (kdim, n), (
            f"exact mode needs per-row scales (K, N); got {scales.shape}")
        scale_spec = pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j))
    else:
        group_size = kdim // scales.shape[0]
        assert group_size % block_k == 0, (
            f"group_size {group_size} must be a multiple of block_k {block_k}")
        scales = scales.reshape(scales.shape[0], 1, n)
        scale_spec = pl.BlockSpec(
            (None, 1, block_n),
            lambda i, j, k, gs=group_size, bk=block_k: (k * bk // gs, 0, j))
    nk = kdim // block_k

    grid = (m // block_m, n // block_n, nk)

    kernel = functools.partial(_kernel, nk=nk, out_dtype=out_dtype,
                               compute_dtype=compute_dtype,
                               exact_dequant=exact_dequant, has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, k: (k, j)),
            scale_spec,
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, packed, scales, bias)
