"""Jit'd public wrappers for the Pallas kernels (padding, defaults, backend
dispatch). ``interpret=True`` is selected automatically off-TPU so the same
call sites work in CI (CPU) and production (TPU)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import cascade_matmul as _cm
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fit_block(dim: int, cap: int) -> tuple[int, int]:
    """``(padded_dim, block)`` for one blocked kernel dimension.

    A dim that fits in ``cap`` is one block (a block equal to the whole
    array dim always meets Mosaic's (8, 128) rule). A larger dim is padded
    up to a multiple of ``align = min(128, cap)`` and gets the largest
    multiple of ``align`` that divides the padded dim and is at most
    ``cap`` — e.g. 13440 = 105 * 128 gives 384 at cap 512 — so the VMEM
    footprint is bounded by the cap for every dimension."""
    if dim <= cap:
        return dim, dim
    align = min(128, cap)
    padded = -(-dim // align) * align
    block = max(b for b in range(align, cap + 1, align) if padded % b == 0)
    return padded, block


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret",
                     "exact_dequant"),
)
def cascade_matmul(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    bias: jax.Array | None = None,
    *,
    block_m: int = 256,
    block_n: int = 1024,
    block_k: int = 2048,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    exact_dequant: bool | None = None,
) -> jax.Array:
    """FP4-packed weight matmul: x (.., K) @ Wq (K, N) -> (.., N).

    Leading dims of x are flattened to M. The blocks follow the call's
    shapes: the whole M is one block up to ``block_m`` (so each weight tile
    is decoded once, for the decode grid and the extend chunk alike), else
    blocks of ``block_m``. N and K get their blocks from :func:`fit_block`
    with caps ``block_n`` / ``block_k`` (K blocks must also divide the
    scale group); an unaligned N or per-column-scale K is zero-padded,
    never blocked whole. The caps keep a grid step's codes at most
    ``block_n * block_k / 2`` bytes (1 MiB by default): large enough that
    the per-step overhead is small against the decode, small enough for
    VMEM at M = 256. Odd-K weights (quantize_weight zero-row pad-to-pack)
    are handled by padding the activations with a matching zero column.

    ``exact_dequant`` defaults to the resolved ``interpret`` value: compiled
    (TPU) runs the fast bf16-MXU kernel; interpret mode (CPU/CI) runs one
    block whose kernel body performs the same dequantize ->
    dot -> bias operations as the jnp serving path on the same shapes, so
    results are bit-identical to ``cascade.linear_apply``'s XLA branch —
    the fused serving path's token-exactness contract.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if exact_dequant is None:
        exact_dequant = interpret
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = packed.shape[1]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    if packed.shape[0] * 2 == kdim + 1:
        # odd-K weights carry quantize_weight's zero pad row: give the
        # activations a matching zero column (contributes nothing to the dot)
        x2 = jnp.pad(x2, ((0, 0), (0, 1)))
        kdim += 1
    bias2 = jnp.zeros((1, n), jnp.float32) if bias is None else bias.reshape(1, n).astype(jnp.float32)
    if exact_dequant:
        assert interpret, "exact_dequant is the interpret-mode parity path"
        # one block on the unpadded operands (interpret mode needs no block
        # alignment); per-row scales broadcast exactly like the jnp
        # dequant's group reshape
        group = kdim // scales.shape[0]
        s_full = jnp.repeat(scales, group, axis=0)          # (K, N)
        out = _cm.cascade_matmul_exact_pallas(
            x2, packed, s_full, bias2,
            out_dtype=out_dtype, has_bias=bias is not None,
        )
        return out.reshape(*lead, n)
    bm = min(block_m, -(-m // 8) * 8)
    x2 = _pad_to(x2, 0, bm)
    n_pad, bn = fit_block(n, block_n)
    groups = scales.shape[0]
    if groups == 1:
        k_pad, bk = fit_block(kdim, block_k)
    else:
        group = kdim // groups
        group_pad, bk = fit_block(group, block_k)
        if group_pad != group:
            raise ValueError(f"group size {group} has no block that is a "
                             f"multiple of {min(128, block_k)}")
        k_pad = kdim
    if k_pad > kdim:   # zero codes decode to 0: padded rows add nothing
        x2 = _pad_to(x2, 1, k_pad)
        packed = _pad_to(packed, 0, k_pad // 2)
    if n_pad > n:
        packed = _pad_to(packed, 1, n_pad)
        scales = _pad_to(scales, 1, n_pad)
        bias2 = _pad_to(bias2, 1, n_pad)
    out = _cm.cascade_matmul_pallas(
        x2, packed, scales, bias2,
        block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:m, :n].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked attention, GQA-aware. q: (B,Hq,S,D), k/v: (B,Hkv,S,D)."""
    if interpret is None:
        interpret = not _on_tpu()
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("scale", "block_t", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    *,
    scale: float | None = None,
    block_t: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-step attention on a stacked cache. q: (B, Hq, D) — one query
    token per slot; k/v: (B, T, Hkv, D) cache buffers; valid: (B, T) nonzero
    where the slot holds a real key. Returns (B, Hq, D) f32.

    Interpret mode (CPU/CI) runs the exact single-block kernel —
    bit-identical to the jnp decode attention in ``layers.attn_apply``;
    compiled (TPU) streams over T blocks with running-softmax scratch."""
    if interpret is None:
        interpret = not _on_tpu()
    return _fa.decode_attention_pallas(
        q, k, v, valid, scale=scale, block_t=fit_block(k.shape[1], block_t)[1],
        exact=interpret, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 64, interpret: bool | None = None):
    """Per-head SSD recurrence (inputs pre-broadcast per head). (BH,S,P)."""
    if interpret is None:
        interpret = not _on_tpu()
    return _ssd.ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode(x, dt, A, B, C, D, state, *, interpret: bool | None = None):
    """One-token SSD recurrence on the stacked decode cache — the serving
    decode step routed through the scan kernel at ``s = chunk = 1`` with
    the slot states as the carried initial state.

    Shapes mirror ``models.ssm.ssd_decode_step``: x (B,1,H,P); dt (B,1,H)
    (post-softplus, f32); A (H,); B/C (B,1,G,N); D (H,); state (B,H,P,N)
    f32. Returns (y (B,1,H,P) in x.dtype, new_state f32). The kernel body
    performs the identical decay/update/readout arithmetic on the identical
    f32 operands, so interpret mode (CPU/CI) is bit-exact with the jnp
    recurrence — the fused serving path's token-exactness contract.
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, _, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hb = h // g
    xf = x[:, 0].astype(jnp.float32).reshape(b * h, 1, p)
    dtf = dt[:, 0].astype(jnp.float32).reshape(b * h, 1)
    Bh = jnp.repeat(B[:, 0].astype(jnp.float32), hb, axis=1).reshape(b * h, 1, n)
    Ch = jnp.repeat(C[:, 0].astype(jnp.float32), hb, axis=1).reshape(b * h, 1, n)
    Af = jnp.broadcast_to(A.astype(jnp.float32), (b, h)).reshape(b * h)
    Df = jnp.broadcast_to(D.astype(jnp.float32), (b, h)).reshape(b * h)
    y, fin = _ssd.ssd_scan_pallas(
        xf, dtf, Af, Bh, Ch, Df, chunk=1, interpret=interpret,
        initial_state=state.reshape(b * h, p, n), return_final_state=True)
    return (y.reshape(b, h, p)[:, None].astype(x.dtype),
            fin.reshape(b, h, p, n))


# Re-exported oracles (tests and low-stakes call sites)
cascade_matmul_ref = _ref.cascade_matmul_ref
flash_attention_ref = _ref.flash_attention_ref
decode_attention_ref = _ref.decode_attention_ref
ssd_scan_ref = _ref.ssd_scan_ref
