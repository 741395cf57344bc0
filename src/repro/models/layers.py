"""Shared neural-net layers for all assigned architectures.

Functional style: ``*_init(key, ...) -> params`` and ``*_apply(params, x, ...)``.
Every dense projection goes through :mod:`repro.core.cascade` so the paper's
FP4 serving format / QAT / column-parallel distribution apply uniformly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import cascade
from repro.core.cascade import CascadeConfig
from repro.distributed.sharding import constrain_attn_queries, constrain_matmul_input
from repro.models import cache_utils


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(d: int, norm_type: str = "rmsnorm") -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(params: dict, x: jax.Array, norm_type: str = "rmsnorm", eps: float = 1e-6) -> jax.Array:
    # CASCADE: norms sit at the between-layers boundary where activations
    # are gathered (paper Section 13.4) — pin features replicated so the
    # mean/variance reduction is local. Without this, GSPMD can let a
    # column-sharded branch output win the residual-add sharding and the
    # feature reduction becomes a (scalar, but nonzero) partial-sum
    # all-reduce. No-op without an installed cascade policy.
    x = constrain_matmul_input(x)
    xf = x.astype(jnp.float32)
    if norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (incl. partial-rotary and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0) -> jax.Array:
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv  # (rot/2,)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(factor) + 1``
    (1 for a factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dim: int, base: float, original_max: int, beta_fast: float,
               beta_slow: float) -> tuple[int, int]:
    """The rotary pairs ``[low, high]`` over which YaRN ramps from the
    original frequencies to the interpolated ones: pair ``i`` rotates
    ``original_max / (2 pi base^(2i/dim))`` times over the original context,
    and ``low`` / ``high`` are where that count is ``beta_fast`` /
    ``beta_slow`` (floor / ceil, clamped to ``[0, dim - 1]``)."""
    def corr(rot):
        return dim * math.log(original_max / (rot * 2 * math.pi)) / (2 * math.log(base))

    return max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_freqs(dim: int, base: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN ``inv_freq`` (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``):
    ``base^(-2i/dim)`` below ``low``, the same over ``factor`` above
    ``high``, a linear ramp between."""
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    low, high = yarn_range(dim, base, original_max, beta_fast, beta_slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
               mscale: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    2*len(inv_freq) channels, passes the rest through (partial rotary).
    ``mscale`` multiplies cos and sin (YaRN)."""
    rot2 = inv_freq.shape[0]
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (B, S, r/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x_rot, x_pass = x[..., : 2 * rot2], x[..., 2 * rot2:]
    x1, x2 = x_rot[..., :rot2], x_rot[..., rot2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def apply_mrope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
                sections: tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE. positions: (3, B, S) for (t, h, w); the
    inv_freq bands are split into ``sections`` (in half-dim units), each band
    rotated by its own position stream (arXiv:2409.12191)."""
    rot2 = inv_freq.shape[0]
    ang_all = positions[..., None].astype(jnp.float32) * inv_freq  # (3, B, S, r/2)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[i % 3, :, :, start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)  # (B, S, r/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x_rot, x_pass = x[..., : 2 * rot2], x[..., 2 * rot2:]
    x1, x2 = x_rot[..., :rot2], x_rot[..., rot2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# per-slot cache primitives (continuous batching)
# ---------------------------------------------------------------------------

def pos_rows(pos: jax.Array, b: int) -> jax.Array:
    """Normalize a cache position to per-row shape (B,) int32.

    Caches written by this module carry one position per batch row so a
    stacked slot grid can hold streams of different lengths (continuous
    batching); a legacy scalar position is broadcast.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    return pos


def pos_slots(slot_pos: jax.Array, b: int, t: int) -> jax.Array:
    """Normalize a ring-buffer slot-position table to per-row shape (B, T)."""
    slot_pos = jnp.asarray(slot_pos, jnp.int32)
    if slot_pos.ndim == 1:
        slot_pos = jnp.broadcast_to(slot_pos, (b, t))
    return slot_pos


def update_rows(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write ``new[i]`` into ``buf[i]`` at row-specific index ``idx[i]``.

    buf: (B, T, ...); new: (B, s, ...); idx: (B,) per-row start along axis 1.
    """
    def one(bu, ne, i):
        start = (i,) + (0,) * (bu.ndim - 1)
        return jax.lax.dynamic_update_slice(bu, ne.astype(bu.dtype), start)

    return jax.vmap(one)(buf, new, idx)


# ---------------------------------------------------------------------------
# attention (GQA / MHA / local-window), full-seq and cached-decode paths
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    window: int = 0              # 0 = full causal; >0 = local attention window
    mrope_sections: tuple = ()   # Qwen2-VL
    softmax_scale: Optional[float] = None
    q_chunk: int = 0             # chunked attention for long prefill (0 = off)


def attn_init(key: jax.Array, cfg: AttnConfig, ccfg: CascadeConfig) -> dict:
    ks = jax.random.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": cascade.linear_init(ks[0], d, h * hd, ccfg, use_bias=cfg.qkv_bias),
        "wk": cascade.linear_init(ks[1], d, hk * hd, ccfg, use_bias=cfg.qkv_bias),
        "wv": cascade.linear_init(ks[2], d, hk * hd, ccfg, use_bias=cfg.qkv_bias),
        "wo": cascade.linear_init(ks[3], h * hd, d, ccfg),
    }


def _sdpa(q, k, v, mask, scale):
    """q: (B,S,H,D), k/v: (B,T,Hkv,D), mask: (S, T) bool or None."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    dv = v.shape[-1]  # may differ from q head dim (MLA)
    qf = constrain_attn_queries(q.astype(jnp.float32)).reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bshgd,bthd->bhgst", qf, k.astype(jnp.float32)) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return o.reshape(b, s, h, dv)


def _chunked_causal_sdpa(q, k, v, scale, q_chunk, window, q_offset=0):
    """Online-softmax attention over query chunks: memory O(q_chunk * T)
    instead of O(S * T). Pure jnp + lax.map — the XLA analogue of the flash
    kernel, used at lowering time for 32k prefill where the naive (S,S)
    logits tensor would be petabytes."""
    b, s, h, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    nq = s // q_chunk
    qc = q.reshape(b, nq, q_chunk, hkv, g, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    def one_chunk(i):
        qi = constrain_attn_queries(qc[:, i].astype(jnp.float32))  # (B, qc, Hkv, g, D)
        logits = jnp.einsum("bshgd,bthd->bhgst", qi, kf) * scale
        rows = q_offset + i * q_chunk + jnp.arange(q_chunk)
        cols = jnp.arange(t)
        m = rows[:, None] >= cols[None, :]
        if window > 0:
            m &= (rows[:, None] - cols[None, :]) < window
        logits = jnp.where(m[None, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhgst,bthd->bshgd", p, vf).reshape(b, q_chunk, h, vf.shape[-1])

    out = jax.lax.map(one_chunk, jnp.arange(nq))  # (nq, B, qc, H, Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def attn_apply(
    params: dict,
    x: jax.Array,
    cfg: AttnConfig,
    ccfg: CascadeConfig,
    positions: jax.Array | None = None,
    cache: dict | None = None,
    mode: str = "full",
    max_len: int | None = None,
    n_valid=None,
) -> tuple[jax.Array, dict | None]:
    """Attention with four modes:

    * ``full``    — causal (optionally windowed) self-attention, no cache.
    * ``prefill`` — same compute as ``full`` but also RETURNS a KV cache
                    (ring-aligned for windowed archs) ready for decode.
    * ``decode``  — single new token (s==1) against the cache; the cache
                    buffer length equals the dry-run shape's seq_len for
                    full attention, or the window for local attention
                    (ring buffer, slot(p) = p %% window).
    * ``extend``  — append s tokens at each row's position (chunked prefill
                    into an existing cache). Rows may sit at different
                    positions: this is the continuous-batching admission
                    path. Only the first ``n_valid`` chunk tokens are real;
                    for full attention the pad K/V lands above the valid
                    region (mask-invalid, overwritten by the next write),
                    for ring buffers pad writes are dropped outright so
                    they can never clobber in-window entries.

    Cache positions are per-row (B,) so a stacked slot grid can hold streams
    of different lengths; legacy scalar positions are broadcast.
    """
    b, s, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = cascade.linear_apply(params["wq"], x, ccfg).reshape(b, s, h, hd)
    k = cascade.linear_apply(params["wk"], x, ccfg).reshape(b, s, hk, hd)
    v = cascade.linear_apply(params["wv"], x, ccfg).reshape(b, s, hk, hd)

    if positions is None:
        if cache is not None:
            positions = pos_rows(cache["pos"], b)[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        else:
            positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    inv = rope_freqs(hd, cfg.rope_theta, cfg.rope_fraction)
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, inv, cfg.mrope_sections)
        k = apply_mrope(k, positions, inv, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, inv)
        k = apply_rope(k, positions, inv)

    scale = cfg.softmax_scale or 1.0 / (hd ** 0.5)

    if mode in ("decode", "extend"):
        assert cache is not None
        assert mode == "extend" or s == 1
        # CASCADE serving layout: q/k/v keep batch over data with features
        # replicated over model. Without this pin, GSPMD may carry the
        # column-sharded projection output through the (b,s,H*hd)->(b,s,H,hd)
        # reshape onto the HEAD dim (n_kv_heads=1 ring caches force it onto
        # head_dim), and the score contraction over a sharded head_dim would
        # emit exactly the partial-sum all-reduce the policy abolishes.
        # No-op without an installed cascade policy.
        q = constrain_matmul_input(q)
        k = constrain_matmul_input(k)
        v = constrain_matmul_input(v)
        pos = pos_rows(cache["pos"], b)                 # (B,) next write index
        bt = cache.get("block_table")                   # (B, nb) => paged pool
        ps_page = cache["k"].shape[1] if bt is not None else 0
        t = bt.shape[-1] * ps_page if bt is not None else cache["k"].shape[1]
        nv = jnp.asarray(s if n_valid is None else n_valid, jnp.int32)
        if bt is not None:
            # paged pool: scatter the new K/V through the block table, then
            # gather the slot's pages back into the SAME dense (B, T, ...)
            # view the dense branch attends over. Rows backed by the trash
            # page are garbage but sit above pos — the -1e30 mask zeroes
            # them exactly, so this path is bit-identical to the dense one.
            assert cfg.window == 0, "paged attention requires full attention"
            ck = cache_utils.paged_update_rows(cache["k"], k, bt, pos, ps_page)
            cv = cache_utils.paged_update_rows(cache["v"], v, bt, pos, ps_page)
            rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]
            new_cache = {"k": ck, "v": cv, "pos": pos + nv}
            att_k = cache_utils.paged_gather(ck, bt, ps_page)
            att_v = cache_utils.paged_gather(cv, bt, ps_page)
        elif cfg.window > 0 and mode == "decode":       # ring buffer, one token
            idx = pos % t
            ck = update_rows(cache["k"], k, idx)
            cv = update_rows(cache["v"], v, idx)
            slot_pos = pos_slots(cache["slot_pos"], b, t)
            slot_pos = jax.vmap(
                lambda sp, p, i: jax.lax.dynamic_update_slice(sp, p[None], (i,)))(
                    slot_pos, pos, idx)                 # (B, T)
            valid = ((slot_pos >= 0) & (slot_pos <= pos[:, None])
                     & (pos[:, None] - slot_pos < cfg.window))[:, None, :]  # (B, 1, T)
            new_cache = {"k": ck, "v": cv, "pos": pos + 1, "slot_pos": slot_pos}
            att_k, att_v = ck, cv
        elif cfg.window > 0:  # ring-buffer chunked extend
            # A chunk may overwrite ring entries that earlier chunk tokens
            # still attend to, so attention runs over [ring, chunk] FIRST
            # and only then are the valid chunk tokens scattered in (write
            # index t = out of bounds = dropped). s <= t keeps within-chunk
            # ring writes collision-free.
            assert s <= t, "prefill chunk must fit inside the ring buffer"
            slot_pos = pos_slots(cache["slot_pos"], b, t)
            rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # (B, s)
            key_pos = jnp.concatenate([slot_pos, rows], axis=1)             # (B, T+s)
            att_k = jnp.concatenate([cache["k"].astype(jnp.float32),
                                     k.astype(jnp.float32)], axis=1)
            att_v = jnp.concatenate([cache["v"].astype(jnp.float32),
                                     v.astype(jnp.float32)], axis=1)
            valid = ((key_pos[:, None, :] >= 0)
                     & (key_pos[:, None, :] <= rows[:, :, None])
                     & (rows[:, :, None] - key_pos[:, None, :] < cfg.window))
            widx = jnp.where(jnp.arange(s, dtype=jnp.int32)[None, :] < nv,
                             rows % t, t)               # (B, s); t => dropped
            scat = lambda bu, ne, ix: bu.at[ix].set(ne.astype(bu.dtype), mode="drop")
            ck = jax.vmap(scat)(cache["k"], k, widx)
            cv = jax.vmap(scat)(cache["v"], v, widx)
            new_slot = jax.vmap(scat)(slot_pos, rows, widx)
            new_cache = {"k": ck, "v": cv, "pos": pos + nv, "slot_pos": new_slot}
        else:
            ck = update_rows(cache["k"], k, pos)
            cv = update_rows(cache["v"], v, pos)
            rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # (B, s)
            valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]        # (B, s, T)
            new_cache = {"k": ck, "v": cv, "pos": pos + nv}
            att_k, att_v = ck, cv
        if ccfg.use_kernel and mode == "decode" and s == 1:
            # fused serving path: the Pallas decode-attention kernel over the
            # stacked cache (mask-as-validity covers full caches and ring
            # buffers alike). Interpret mode runs the exact single-block
            # kernel, bit-identical to the jnp math below; compiled TPU runs
            # the streaming-softmax kernel. extend/verify chunks (s > 1)
            # keep the jnp path — the fused step is the decode hot loop.
            from repro.kernels import ops  # lazy: keeps dryrun import-light
            o = ops.decode_attention(q[:, 0], att_k, att_v, valid[:, 0],
                                     scale=scale).reshape(b, s, h, hd)
        else:
            qd = q.astype(jnp.float32).reshape(b, s, hk, h // hk, hd)
            logits = jnp.einsum("bshgd,bthd->bhgst", qd, att_k.astype(jnp.float32)) * scale
            # valid: (B, s, T[+s]) (or (B, 1, T) ring) -> broadcast vs (b,hk,g,s,t)
            logits = jnp.where(valid[:, None, None], logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhgst,bthd->bshgd", p, att_v.astype(jnp.float32)).reshape(b, s, h, hd)
    else:
        if cfg.q_chunk > 0 and s > cfg.q_chunk:
            o = _chunked_causal_sdpa(q, k, v, scale, cfg.q_chunk, cfg.window)
        else:
            rows = jnp.arange(s)
            m = rows[:, None] >= rows[None, :]
            if cfg.window > 0:
                m &= (rows[:, None] - rows[None, :]) < cfg.window
            o = _sdpa(q, k, v, m, scale)
        new_cache = None
        if mode == "prefill":
            new_cache = _build_cache_from_prefill(k, v, cfg, s, max_len=max_len,
                                                  dtype=ccfg.resolved_kv_dtype)

    out = cascade.linear_apply(params["wo"], o.astype(x.dtype).reshape(b, s, h * hd), ccfg)
    return out, new_cache


def _build_cache_from_prefill(k: jax.Array, v: jax.Array, cfg: AttnConfig, s: int,
                              max_len: int | None = None,
                              dtype=None) -> dict:
    """Construct a decode-ready cache from prefill K/V (positions 0..s-1).

    Positions are per-row (all rows start at s); ``dtype`` overrides the KV
    storage dtype (CascadeConfig.kv_dtype plumbing — fp8 halves decode HBM).
    """
    b, _, hk, hd = k.shape
    if dtype is not None:
        k, v = k.astype(dtype), v.astype(dtype)
    pos = jnp.full((b,), s, jnp.int32)
    if cfg.window > 0:
        t = cfg.window
        if s >= t:
            k_last, v_last = k[:, s - t:], v[:, s - t:]
            pos_last = jnp.arange(s - t, s, dtype=jnp.int32)
        else:
            pad = [(0, 0), (0, t - s), (0, 0), (0, 0)]
            k_last, v_last = jnp.pad(k, pad), jnp.pad(v, pad)
            pos_last = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                                        jnp.full((t - s,), -1, jnp.int32)])
        shift = s % t if s >= t else 0
        return {
            "k": jnp.roll(k_last, shift, axis=1),
            "v": jnp.roll(v_last, shift, axis=1),
            "slot_pos": jnp.broadcast_to(jnp.roll(pos_last, shift), (b, t)),
            "pos": pos,
        }
    t = max_len if max_len is not None else s
    pad = [(0, 0), (0, t - s), (0, 0), (0, 0)]
    return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad), "pos": pos}


def attn_cache_init(batch: int, max_len: int, cfg: AttnConfig, dtype=jnp.bfloat16) -> dict:
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.window > 0:
        # the ring must hold the FULL attention window regardless of
        # max_len: windowed state is O(window), and a shorter ring would
        # silently truncate attention for prompts beyond max_len (matching
        # _build_cache_from_prefill, which also allocates t = window)
        t = cfg.window
        return {
            "k": jnp.zeros((batch, t, hk, hd), dtype),
            "v": jnp.zeros((batch, t, hk, hd), dtype),
            "slot_pos": jnp.full((batch, t), -1, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, hk, hd), dtype),
        "v": jnp.zeros((batch, max_len, hk, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def attn_cache_init_paged(batch: int, num_pages: int, page_size: int,
                          cfg: AttnConfig, dtype=jnp.bfloat16) -> dict:
    """Paged-pool attention cache: K/V live in a shared page pool instead of
    per-slot dense rows. The (B, nb) block table is NOT a cache leaf — the
    host owns it and threads it in per step via the batch dict (page
    allocation is a host decision; the device cache stays donate-safe)."""
    assert cfg.window == 0, "paged attention requires full attention"
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_pages, page_size, hk, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, hk, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key: jax.Array, d: int, d_ff: int, kind: str, ccfg: CascadeConfig) -> dict:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": cascade.linear_init(ks[0], d, d_ff, ccfg),
            "w_up": cascade.linear_init(ks[1], d, d_ff, ccfg),
            "w_down": cascade.linear_init(ks[2], d_ff, d, ccfg),
        }
    # relu2 (nemotron squared-ReLU) / gelu (musicgen)
    return {
        "w_up": cascade.linear_init(ks[0], d, d_ff, ccfg),
        "w_down": cascade.linear_init(ks[1], d_ff, d, ccfg),
    }


def mlp_apply(params: dict, x: jax.Array, kind: str, ccfg: CascadeConfig) -> jax.Array:
    if kind == "swiglu":
        g = cascade.linear_apply(params["w_gate"], x, ccfg)
        u = cascade.linear_apply(params["w_up"], x, ccfg)
        h = jax.nn.silu(g) * u
    elif kind == "geglu":
        g = cascade.linear_apply(params["w_gate"], x, ccfg)
        u = cascade.linear_apply(params["w_up"], x, ccfg)
        h = jax.nn.gelu(g) * u
    elif kind == "relu2":
        u = cascade.linear_apply(params["w_up"], x, ccfg)
        h = jnp.square(jax.nn.relu(u))
    elif kind == "gelu":
        u = cascade.linear_apply(params["w_up"], x, ccfg)
        h = jax.nn.gelu(u)
    else:
        raise ValueError(kind)
    return cascade.linear_apply(params["w_down"], h, ccfg)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed_apply(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def sinusoidal_positions(s: int, d: int, offset=0) -> jax.Array:
    pos = jnp.arange(s, dtype=jnp.float32)[:, None] + offset
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(jnp.bfloat16)
