"""Blocked (flash) attention — Pallas TPU kernels.

Two kernels:

* ``flash_attention_pallas`` — the prefill hot-spot: streaming-softmax
  self-attention with GQA head mapping. Grid is (batch*q_heads, q_blocks,
  k_blocks) with the running max / denominator / accumulator held in VMEM
  scratch across the (sequential) k dimension — the same "partial results
  never leave the chip" dataflow CASCADE uses for matmul columns, applied
  to attention rows.
* ``decode_attention_pallas`` — the serving decode step: ONE query token per
  batch row against a stacked (B, T, Hkv, D) cache with a per-slot validity
  mask (ring buffers and full caches both reduce to "mask says which of the
  T slots hold real keys"). Streams over T blocks when compiled; the
  ``exact`` mode instead runs one program over the whole batch performing
  literally the jnp decode math (mask -> softmax -> value einsums), which
  is what makes the fused serving path token-exact with the XLA path in
  interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, nk, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: block fully masked iff first q row < first k col.

    @pl.when((not causal) or (iq * block_q + block_q - 1 >= ik * block_k))
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    nq, nk = s // block_q, s // block_k

    qf = q.reshape(b * hq, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)

    def kv_index(bh, iq, ik):
        return ((bh // hq) * hkv + (bh % hq) // group, ik, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, nk=nk, block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s, d)


def _decode_exact_kernel(q_ref, k_ref, v_ref, mk_ref, o_ref, *, scale, hkv: int):
    # one program over the FULL batched shapes, performing verbatim the jnp
    # decode math of models.layers.attn_apply (same einsum/batched-dot
    # lowerings, same masked-softmax ordering) — interpret-mode results are
    # bit-identical to the XLA path, which is the fused serving path's
    # token-exactness contract
    b, hq, d = q_ref.shape
    g = hq // hkv
    qd = q_ref[...].astype(jnp.float32).reshape(b, 1, hkv, g, d)
    logits = jnp.einsum("bshgd,bthd->bhgst", qd,
                        k_ref[...].astype(jnp.float32)) * scale
    logits = jnp.where((mk_ref[...] != 0)[:, None, None, None, :],
                       logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", p, v_ref[...].astype(jnp.float32))
    o_ref[...] = o.reshape(b, hq, d)


def _decode_kernel(q_ref, k_ref, v_ref, mk_ref, o_ref, mx_ref, l_ref, acc_ref,
                   *, scale, nt: int):
    it = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)            # (1, d)
    k = k_ref[0].astype(jnp.float32)            # (bt, d)
    v = v_ref[0].astype(jnp.float32)
    live = mk_ref[0] != 0                       # (1, bt)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1, bt)
    s = jnp.where(live, s, _NEG_INF)

    @pl.when(it == 0)
    def _init():
        mx_ref[...] = jnp.full_like(mx_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = mx_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # a fully-masked block leaves m_new at -inf and exp(s - m_new) at
    # exp(0) = 1; zeroing p under the mask keeps the degenerate block from
    # polluting the denominator (decode always has >= 1 live slot overall)
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    mx_ref[...] = m_new

    @pl.when(it == nt - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
    *,
    scale: float | None = None,
    block_t: int = 512,
    exact: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """One-token attention against a stacked KV cache.

    q: (B, Hq, D) — the decode step's single query token per row;
    k/v: (B, T, Hkv, D) — the cache layout ``layers.attn_apply`` holds;
    mask: (B, T), nonzero where the slot holds a real key (full caches:
    position <= current; ring buffers: in-window slots). GQA via head-group
    broadcast. Returns (B, Hq, D) f32.

    ``exact=True`` runs ONE program over the whole batch performing verbatim
    the jnp decode einsum math — interpret-mode results are then
    bit-identical to the jnp decode attention. The default streams over T
    blocks with running max/denominator scratch (the compiled TPU path).
    """
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if exact:
        # one program over the whole batch: replicates the jnp decode math
        # verbatim (bit-identical in interpret mode); whole-array refs, no
        # blocking — splitting per (b, h) changes the batched-dot lowering
        # and costs a few ULPs on some shapes
        return pl.pallas_call(
            functools.partial(_decode_exact_kernel, scale=scale, hkv=hkv),
            out_shape=jax.ShapeDtypeStruct((b, hq, d), jnp.float32),
            interpret=interpret,
        )(q, k, v, mask.astype(jnp.int32))
    block_t = min(block_t, t)
    rem = (-t) % block_t
    if rem:  # pad T to a block multiple; padded slots are masked out
        k = jnp.pad(k, ((0, 0), (0, rem), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, rem), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, rem)))
        t += rem
    nt = t // block_t

    qf = q.reshape(b * hq, 1, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    # (B, 1, T): a (1, block_t) mask block then spans the full second-minor
    # dim, which the (8, 128) block rule accepts for any B
    mk = mask.astype(jnp.int32).reshape(b, 1, t)

    def kv_index(bh, it):
        return ((bh // hq) * hkv + (bh % hq) // group, it, 0)

    kernel = functools.partial(_decode_kernel, scale=scale, nt=nt)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nt),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, it: (bh, 0, 0)),
            pl.BlockSpec((1, block_t, d), kv_index),
            pl.BlockSpec((1, block_t, d), kv_index),
            pl.BlockSpec((1, 1, block_t), lambda bh, it: (bh // hq, 0, it)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, it: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, mk)
    return out.reshape(b, hq, d)
