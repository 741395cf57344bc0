"""Mixture-of-Experts LMs: DeepSeek-V2 (MLA + shared/routed experts, top-6)
and OLMoE (GQA + 64 routed experts, top-8).

Routing uses capacity-based scatter dispatch (no (T, E, C) one-hot tensor —
the dispatch buffer is built with a scatter-add and read back with a gather,
so memory is O(T*E) ints + O(E*C*d) activations; both shard cleanly: tokens
on the ``data`` axis, experts on the ``model`` axis = expert parallelism).

MLA (multi-head latent attention, arXiv:2405.04434): KV compressed to a
512-dim latent + 64-dim decoupled RoPE key. Decode uses the weight-absorption
identity (scores = (q W_k)·c_kv) so the cache stays in latent space —
(kv_lora + rope) bytes/token instead of 2*H*head_dim.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cascade
from repro.core.cascade import CascadeConfig
from repro.configs.base import ArchConfig
from repro.distributed.sharding import (constrain_expert_buffer,
                                        constrain_matmul_input,
                                        constrain_replicated,
                                        constrain_residual)
from repro.models import layers as L
from repro.models.cache_utils import (StackedCacheMixin, paged_gather,
                                      paged_rows_restore, paged_rows_snapshot,
                                      paged_update_rows, seq_rows_restore,
                                      seq_rows_snapshot, take_last_valid)


def _remat_policy(name: str):
    import jax as _jax
    return {
        "dots": _jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "none": _jax.checkpoint_policies.nothing_saveable,
        "save_all": _jax.checkpoint_policies.everything_saveable,
    }[name]


# ---------------------------------------------------------------------------
# routed-expert FFN
# ---------------------------------------------------------------------------

def moe_ffn_init(key: jax.Array, cfg: ArchConfig, ccfg: CascadeConfig) -> dict:
    ks = jax.random.split(key, 5)
    d, e, dff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": jax.random.normal(ks[0], (d, cfg.router_width), jnp.float32) * 0.02,
        "wg": cascade.expert_linear_init(ks[1], e, d, dff, ccfg),
        "wu": cascade.expert_linear_init(ks[2], e, d, dff, ccfg),
        "wd": cascade.expert_linear_init(ks[3], e, dff, d, ccfg),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, cfg.n_shared_experts * dff, "swiglu", ccfg)
    return p


def route(xf: jax.Array, router: jax.Array, cfg: ArchConfig):
    """Routing of (T, d) tokens over every expert the router scores, held
    here or not: ``(gates, idx)``, each (T, top_k), with global expert ids.

    Softmax scores. With ``moe_groups > 1``, group-limited greedy: a group's
    score is its best expert's, the ``moe_topk_groups`` best groups stay and
    the rest are zeroed, and the top-k are taken from what is left. Gates
    are renormalised to sum 1 (``moe_renorm``), else multiplied by
    ``moe_scale``. The router runs in float32 (``HIGHEST``: a TPU would
    otherwise round its operands to bfloat16 and flip near-tied picks)."""
    probs = jax.nn.softmax(jnp.dot(xf.astype(jnp.float32), router,
                                   precision=lax.Precision.HIGHEST), axis=-1)
    if cfg.moe_groups > 1:
        t, e = probs.shape
        g = cfg.moe_groups
        best = jnp.max(probs.reshape(t, g, e // g), axis=-1)             # (T, G)
        _, top = lax.top_k(best, cfg.moe_topk_groups)
        kept = jnp.any(top[:, :, None] == jnp.arange(g), axis=1)          # (T, G)
        probs = jnp.where(jnp.repeat(kept, e // g, axis=1), probs, 0.0)
    gates, idx = lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_renorm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    elif cfg.moe_scale != 1.0:
        gates = gates * cfg.moe_scale
    return gates, idx


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    c = math.ceil(cfg.moe_capacity_factor * n_tokens * cfg.moe_top_k / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def moe_ffn_apply(params: dict, x: jax.Array, cfg: ArchConfig, ccfg: CascadeConfig,
                  no_drop: bool = False, routes: bool = False):
    """Capacity-dispatched routed experts, this chip's share of them.

    The router scores all ``cfg.router_width`` experts; this chip holds
    ``n_experts`` of them, from ``first_held_expert``. Assignments to the
    experts it does not hold are dropped before dispatch, so the buffer,
    its capacity and the expert matmuls cover the held experts only, and
    the result is the held experts' part plus the shared experts. With
    ``routes``, returns ``(y, routed)``: routed (T, n_experts) bool, which
    held experts each token went to.

    ``no_drop=True`` (serving paths) sizes the buffer for worst-case
    routing skew so NO token is ever capacity-dropped: per-token outputs
    then depend only on that token, never on batch composition — which is
    what makes batched/chunked decode token-exact against the slot-wise
    reference. top_k experts are DISTINCT per token, so one expert can
    receive at most t assignments — capacity t suffices. Training keeps
    the ``moe_capacity_factor`` drop semantics.
    """
    b, s, d = x.shape
    t = b * s
    k, e = cfg.moe_top_k, cfg.n_experts
    cap = (-(-t // 8) * 8) if no_drop else _capacity(t, cfg)
    xf = x.reshape(t, d)
    if no_drop:
        # serving (decode/extend) token counts are tiny: replicate them over
        # the mesh before the dispatch scatter so the buffer is built locally
        # on every shard — no cross-shard scatter-add, hence no partial-sum
        # all-reduce in the cascade decode step (no-op without a mesh policy;
        # train keeps data-sharded tokens and the documented dispatch reduce)
        xf = constrain_replicated(xf)

    gates, idx = route(xf, params["router"], cfg)                    # (T, k)
    share = cfg.router_width != e
    if share:
        # held experts by their index here; e (no buffer row) for the rest
        idx = idx - cfg.first_held_expert
        idx = jnp.where((idx >= 0) & (idx < e), idx, e)

    flat_e = idx.reshape(-1)                                          # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    at = jnp.minimum(flat_e, e - 1) if share else flat_e
    pos_in_e = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                   at[:, None], axis=1)[:, 0]         # (T*k,)
    keep = pos_in_e < cap
    if share:
        keep = keep & (flat_e < e)
    dst = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)           # OOB = dropped

    xk = jnp.repeat(xf, k, axis=0)                                    # (T*k, d) token-major
    if no_drop and xk.shape[0] * e * cap <= (1 << 22):
        # one-hot dot dispatch for serving-sized token counts: in no_drop
        # mode every assignment owns a UNIQUE buffer row (capacity = t,
        # distinct experts per token), so this is bitwise the scatter below
        # (each output row sums one real value and exact zeros) — but GSPMD
        # provably keeps a dot over replicated operands local, whereas it
        # may partition the scatter-add over data shards and recombine with
        # exactly the partial-sum all-reduce the cascade decode step must
        # not contain. Big chunked-prefill dispatches (where the one-hot
        # would not fit) keep the scatter.
        xk = constrain_replicated(xk)
        sel = jax.nn.one_hot(dst, e * cap, dtype=xk.dtype)            # (T*k, E*C)
        buf = constrain_replicated(jnp.einsum("te,td->ed", sel, xk))
    else:
        buf = jnp.zeros((e * cap, d), xf.dtype).at[dst].add(xk, mode="drop")
    buf = constrain_expert_buffer(buf.reshape(e, cap, d))

    h = jax.nn.silu(cascade.expert_linear_apply(params["wg"], buf, ccfg).astype(jnp.float32))
    h = (h * cascade.expert_linear_apply(params["wu"], buf, ccfg).astype(jnp.float32)).astype(buf.dtype)
    out = constrain_expert_buffer(
        cascade.expert_linear_apply(params["wd"], h, ccfg))           # (E, C, d)

    outf = out.reshape(e * cap, d)
    got = jnp.take(outf, jnp.minimum(dst, e * cap - 1), axis=0)
    got = jnp.where(keep[:, None], got, 0.0)
    y = jnp.sum((got.astype(jnp.float32)
                 * gates.reshape(-1)[:, None]).reshape(t, k, d), axis=1)

    if "shared" in params:
        y = y + L.mlp_apply(params["shared"], xf, "swiglu", ccfg).astype(jnp.float32)
    y = y.reshape(b, s, d).astype(x.dtype)
    if routes:
        return y, jnp.any(idx[:, :, None] == jnp.arange(e), axis=1)
    return y


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_init(key: jax.Array, cfg: ArchConfig, ccfg: CascadeConfig) -> dict:
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {
        "wq_a": cascade.linear_init(ks[0], d, cfg.q_lora, ccfg),
        "q_norm": L.norm_init(cfg.q_lora),
        "wq_b": cascade.linear_init(ks[1], cfg.q_lora, h * qk, ccfg),
        "wkv_a": cascade.linear_init(ks[2], d, cfg.kv_lora + cfg.qk_rope_dim, ccfg),
        "kv_norm": L.norm_init(cfg.kv_lora),
        "wkv_b": cascade.linear_init(ks[3], cfg.kv_lora, h * (cfg.qk_nope_dim + cfg.v_head_dim), ccfg),
        "wo": cascade.linear_init(ks[4], h * cfg.v_head_dim, d, ccfg),
    }
    return p


def _mla_qkr(params, x, cfg, ccfg, positions):
    """Shared q / latent-kv projection + rope. Returns q_nope, q_rope, c_kv, k_rope."""
    b, s, _ = x.shape
    h = cfg.n_heads
    q = cascade.linear_apply(params["wq_b"],
                             L.norm_apply(params["q_norm"],
                                          cascade.linear_apply(params["wq_a"], x, ccfg)),
                             ccfg).reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    kv = cascade.linear_apply(params["wkv_a"], x, ccfg)
    c_kv = L.norm_apply(params["kv_norm"], kv[..., : cfg.kv_lora])
    k_rope = kv[..., cfg.kv_lora:][:, :, None, :]                     # (b,s,1,rope)
    # CASCADE pin (see layers.attn_apply): these are column-sharded
    # projection slices; carried sharded into rope, the concatenate of the
    # rotated halves lowers to a masked cross-shard add, and the score
    # contractions would split. Batch stays over data, features replicate.
    # No-op without an installed cascade policy.
    q_nope = constrain_matmul_input(q_nope)
    q_rope = constrain_matmul_input(q_rope)
    c_kv = constrain_matmul_input(c_kv)
    k_rope = constrain_matmul_input(k_rope)
    inv, mscale = mla_rope(cfg)
    q_rope = L.apply_rope(q_rope, positions, inv, mscale)
    k_rope = L.apply_rope(k_rope, positions, inv, mscale)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_rope(cfg: ArchConfig):
    """``(inv_freq, mscale)`` of the decoupled rope head: YaRN where
    ``yarn_factor`` is set (cos and sin times ``m(mscale) / m(mscale_all_dim)``),
    else plain rope."""
    if not cfg.yarn_factor:
        return L.rope_freqs(cfg.qk_rope_dim, cfg.rope_theta, 1.0), 1.0
    f = cfg.yarn_factor
    inv = L.yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, f, cfg.yarn_original_max,
                       cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return inv, L.yarn_mscale(f, cfg.yarn_mscale) / L.yarn_mscale(f, cfg.yarn_mscale_all_dim)


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``m(mscale_all_dim)^2`` where set."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def mla_apply(params, x, cfg: ArchConfig, ccfg, cache=None, mode="full", max_len=None,
              n_valid=None):
    b, s, _ = x.shape
    h = cfg.n_heads
    scale = mla_softmax_scale(cfg)
    if cache is not None:
        positions = (L.pos_rows(cache["pos"], b)[:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None, :])
    else:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, ccfg, positions)

    wkv_b = cascade.linear_weight(params["wkv_b"], ccfg)              # (kv_lora, H*(nope+v))
    wkv_b = wkv_b.reshape(cfg.kv_lora, h, cfg.qk_nope_dim + cfg.v_head_dim)
    w_k = wkv_b[..., : cfg.qk_nope_dim]                               # (lora, H, nope)
    w_v = wkv_b[..., cfg.qk_nope_dim:]                                # (lora, H, v)

    if mode in ("decode", "extend"):
        # decode: one new token; extend: a (right-padded) chunk of s tokens
        # at each row's position — pad latents land mask-invalid above the
        # valid region and are overwritten by the next write.
        assert mode == "extend" or s == 1
        pos = L.pos_rows(cache["pos"], b)                     # (B,) per-slot
        nv = jnp.asarray(s if n_valid is None else n_valid, jnp.int32)
        bt = cache.get("block_table")
        if bt is not None:
            # paged latent pool: scatter through the block table, gather the
            # slot's pages back into the dense (B, T, ...) view the scores
            # below contract over — bit-identical to the dense path (trash-
            # page rows sit above pos where the -1e30 mask zeroes them).
            ps_page = cache["c_kv"].shape[1]
            ckv_pool = paged_update_rows(cache["c_kv"], c_kv, bt, pos, ps_page)
            krp_pool = paged_update_rows(cache["k_rope"], k_rope, bt, pos, ps_page)
            ckv = paged_gather(ckv_pool, bt, ps_page)
            krp = paged_gather(krp_pool, bt, ps_page)
            t = bt.shape[-1] * ps_page
            new_cache = {"c_kv": ckv_pool, "k_rope": krp_pool, "pos": pos + nv}
        else:
            ckv = L.update_rows(cache["c_kv"], c_kv, pos)
            krp = L.update_rows(cache["k_rope"], k_rope, pos)
            t = ckv.shape[1]
            new_cache = {"c_kv": ckv, "k_rope": krp, "pos": pos + nv}
        rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B, s)
        # weight absorption: stay in latent space
        q_lat = jnp.einsum("bshd,lhd->bshl", q_nope.astype(jnp.float32), w_k.astype(jnp.float32))
        scores = (jnp.einsum("bshl,btl->bhst", q_lat, ckv.astype(jnp.float32))
                  + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32), krp.astype(jnp.float32))) * scale
        valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]       # (B, s, T)
        scores = jnp.where(valid[:, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhst,btl->bshl", p, ckv.astype(jnp.float32))
        o = jnp.einsum("bshl,lhd->bshd", ctx, w_v.astype(jnp.float32))  # (b,s,H,v)
    else:
        # expand latents to per-head keys/values (prefill & train)
        k_nope = jnp.einsum("btl,lhd->bthd", c_kv.astype(jnp.float32), w_k.astype(jnp.float32))
        v = jnp.einsum("btl,lhd->bthd", c_kv.astype(jnp.float32), w_v.astype(jnp.float32))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, cfg.qk_rope_dim))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        cd = ccfg.compute_dtype
        if cfg.q_chunk > 0 and s > cfg.q_chunk:
            o = L._chunked_causal_sdpa(q_full.astype(cd), k_full.astype(cd),
                                       v.astype(cd), scale, cfg.q_chunk, 0)
        else:
            rows = jnp.arange(s)
            m = rows[:, None] >= rows[None, :]
            o = L._sdpa(q_full.astype(cd), k_full.astype(cd), v.astype(cd), m, scale)
        o = o[..., : cfg.v_head_dim]
        new_cache = None
        if mode == "prefill":
            t = max_len if max_len is not None else s
            pad = [(0, 0), (0, t - s), (0, 0)]
            kvd = ccfg.resolved_kv_dtype
            new_cache = {"c_kv": jnp.pad(c_kv.astype(kvd), pad),
                         "k_rope": jnp.pad(k_rope.astype(kvd), pad),
                         "pos": jnp.full((b,), s, jnp.int32)}

    out = cascade.linear_apply(params["wo"], o.astype(x.dtype).reshape(b, s, h * cfg.v_head_dim), ccfg)
    return out, new_cache


def mla_cache_init(batch: int, max_len: int, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def mla_cache_init_paged(batch: int, num_pages: int, page_size: int,
                         cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((num_pages, page_size, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((num_pages, page_size, cfg.qk_rope_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MoE LM (DeepSeek-V2 / OLMoE)
# ---------------------------------------------------------------------------

class MoELM(StackedCacheMixin):
    #: ``decode_step(..., routes=True)`` also returns the held experts' routes
    routes_experts = True

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.use_mla = cfg.kv_lora > 0
        if not self.use_mla:
            self.attn_cfg = L.AttnConfig(
                d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
                rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk)

    # ------------------------------------------------------------------ init
    def _attn_init(self, key, ccfg):
        return (mla_init(key, self.cfg, ccfg) if self.use_mla
                else L.attn_init(key, self.attn_cfg, ccfg))

    def _moe_layer_init(self, key, ccfg):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.norm_init(cfg.d_model, cfg.norm_type),
            "attn": self._attn_init(k1, ccfg),
            "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
            "moe": moe_ffn_init(k2, cfg, ccfg),
        }

    def _dense_layer_init(self, key, ccfg):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.norm_init(cfg.d_model, cfg.norm_type),
            "attn": self._attn_init(k1, ccfg),
            "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.dense_d_ff or cfg.d_ff, "swiglu", ccfg),
        }

    def init_params(self, key, ccfg):
        cfg = self.cfg
        n_moe = cfg.n_layers - cfg.first_dense_layers
        keys = jax.random.split(key, n_moe + cfg.first_dense_layers + 2)
        params = {
            "dense_layers": [self._dense_layer_init(keys[i], ccfg)
                             for i in range(cfg.first_dense_layers)],
            "layers": jax.vmap(lambda k: self._moe_layer_init(k, ccfg))(
                keys[cfg.first_dense_layers: cfg.first_dense_layers + n_moe]),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm_type),
            "embed": L.embed_init(keys[-2], cfg.vocab, cfg.d_model, dtype=ccfg.compute_dtype),
            "lm_head": cascade.linear_init(keys[-1], cfg.d_model, cfg.vocab, ccfg),
        }
        return params

    # --------------------------------------------------------------- blocks
    def _attn_apply(self, lp, x, ccfg, cache, mode, max_len=None, n_valid=None):
        if self.use_mla:
            return mla_apply(lp, x, self.cfg, ccfg, cache, mode, max_len, n_valid=n_valid)
        return L.attn_apply(lp, x, self.attn_cfg, ccfg, cache=cache, mode=mode,
                            max_len=max_len, n_valid=n_valid)

    def _block(self, lp, x, ccfg, cache, mode, moe: bool, max_len=None, n_valid=None,
               routes=False):
        """``(x, cache)``; with ``routes`` (an MoE block), ``(x, cache,
        routed)``: which held experts each token went to."""
        cfg = self.cfg
        h, nc = self._attn_apply(lp["attn"], L.norm_apply(lp["ln1"], x, cfg.norm_type),
                                 ccfg, cache, mode, max_len, n_valid)
        x = x + h
        u = L.norm_apply(lp["ln2"], x, cfg.norm_type)
        if moe:
            # serving modes dispatch drop-free: capacity drops would make a
            # token's output depend on unrelated slots / chunk boundaries,
            # breaking batched-vs-slotwise parity (train keeps drops)
            y = self._moe_ffn(lp["moe"], u, ccfg, no_drop=(mode != "full"),
                              routes=routes)
            if routes:
                y, routed = y
                return constrain_residual(x + y), nc, routed
            x = x + y
        else:
            x = x + L.mlp_apply(lp["mlp"], u, "swiglu", ccfg)
        return constrain_residual(x), nc

    def _moe_ffn(self, lp_moe, u, ccfg, no_drop=False, routes=False):
        """Dispatch strategy: shard_map expert parallelism when the launcher
        installed a policy with moe_ep=True (kills the GSPMD scatter
        all-reduce, see models/moe_shardmap.py); jit capacity-dispatch
        otherwise (CPU tests / no mesh). The EP path keeps capacity
        semantics (it is a training/lowering surface, not the engine's); it
        holds every expert the router scores and returns no routes, so a
        held share or ``routes`` is refused there."""
        from repro.distributed.sharding import get_activation_policy
        pol = get_activation_policy()
        if pol and pol.get("moe_ep") and pol.get("mesh") is not None:
            if routes or self.cfg.router_width != self.cfg.n_experts:
                raise NotImplementedError(
                    "the expert-parallel path holds every expert and returns "
                    "no routes: serve a held share or count routes without moe_ep")
            from repro.models.moe_shardmap import moe_ffn_apply_ep
            return moe_ffn_apply_ep(lp_moe, u, self.cfg, ccfg, pol["mesh"],
                                    batch_axes=pol["batch_axes"])
        return moe_ffn_apply(lp_moe, u, self.cfg, ccfg, no_drop=no_drop, routes=routes)

    # --------------------------------------------------------------- api
    def _head(self, params, x, ccfg):
        x = L.norm_apply(params["final_norm"], x, self.cfg.norm_type)
        return cascade.linear_apply(params["lm_head"], x, ccfg).astype(jnp.float32)

    def forward(self, params, batch, ccfg, remat: bool = False,
                remat_policy: str = "dots"):
        x = L.embed_apply(params["embed"], batch["tokens"])
        for dp in params["dense_layers"]:
            x, _ = self._block(dp, x, ccfg, None, "full", moe=False)

        def body(x, lp):
            y, _ = self._block(lp, x, ccfg, None, "full", moe=True)
            return y, None

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(remat_policy))
        x, _ = lax.scan(body, x, params["layers"])
        return self._head(params, x, ccfg)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg

        def one(_):
            return (mla_cache_init(batch, max_len, cfg, dtype) if self.use_mla
                    else L.attn_cache_init(batch, max_len, self.attn_cfg, dtype))

        n_moe = cfg.n_layers - cfg.first_dense_layers
        return {
            "dense_layers": [one(None) for _ in range(cfg.first_dense_layers)],
            "layers": jax.vmap(one)(jnp.arange(n_moe)),
        }

    # ------------------------------------------------------- paged cache API
    @property
    def paged_attention(self) -> bool:
        return True  # MLA latents and GQA KV both page (full attention)

    def init_paged_cache(self, batch: int, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg

        def one(_):
            return (mla_cache_init_paged(batch, num_pages, page_size, cfg, dtype)
                    if self.use_mla
                    else L.attn_cache_init_paged(batch, num_pages, page_size,
                                                 self.attn_cfg, dtype))

        n_moe = cfg.n_layers - cfg.first_dense_layers
        return {
            "dense_layers": [one(None) for _ in range(cfg.first_dense_layers)],
            "layers": jax.vmap(one)(jnp.arange(n_moe)),
        }

    def paged_copy_page(self, cache: dict, src, dst) -> dict:
        """Copy physical page ``src`` to ``dst`` in every pool leaf (radix
        copy-on-write). Per-layer dense caches carry the page axis first;
        the scanned stack carries it after the layer axis."""
        cp_flat = lambda c: {k: (v if k == "pos" else v.at[dst].set(v[src]))
                             for k, v in c.items()}
        cp_stk = lambda c: {k: (v if k == "pos" else v.at[:, dst].set(v[:, src]))
                            for k, v in c.items()}
        return {"dense_layers": [cp_flat(c) for c in cache["dense_layers"]],
                "layers": cp_stk(cache["layers"])}

    def prefill(self, params, batch, ccfg, max_len: int | None = None):
        x = L.embed_apply(params["embed"], batch["tokens"])
        dense_caches = []
        for dp in params["dense_layers"]:
            x, c = self._block(dp, x, ccfg, None, "prefill", moe=False, max_len=max_len)
            dense_caches.append(c)

        def body(x, lp):
            y, c = self._block(lp, x, ccfg, None, "prefill", moe=True, max_len=max_len)
            return y, c

        x, caches = lax.scan(body, x, params["layers"])
        logits = self._head(params, x[:, -1:], ccfg)
        return logits, {"dense_layers": dense_caches, "layers": caches}

    def decode_step(self, params, batch, cache, ccfg, routes: bool = False):
        """``(logits, cache)``; with ``routes``, ``(logits, cache, routed)``:
        routed (MoE layers, B, n_experts) bool, which held experts each
        MoE layer sent each row to (the engine's routed-work counter)."""
        x = L.embed_apply(params["embed"], batch["tokens"])
        bt = batch.get("block_table")
        new_dense = []
        for dp, dc in zip(params["dense_layers"], cache["dense_layers"]):
            if bt is not None:
                dc = dict(dc, block_table=bt)
            x, nc = self._block(dp, x, ccfg, dc, "decode", moe=False)
            new_dense.append(nc)

        def body(x, scanned):
            lp, c = scanned
            if bt is not None:
                c = dict(c, block_table=bt)
            if routes:
                y, nc, routed = self._block(lp, x, ccfg, c, "decode", moe=True,
                                            routes=True)
                return y, (nc, routed)
            return self._block(lp, x, ccfg, c, "decode", moe=True)

        x, ys = lax.scan(body, x, (params["layers"], cache["layers"]))
        new_caches, routed = ys if routes else (ys, None)
        logits = self._head(params, x, ccfg)
        new_cache = {"dense_layers": new_dense, "layers": new_caches}
        return (logits, new_cache, routed) if routes else (logits, new_cache)

    def prefill_extend(self, params, batch, cache, ccfg, n_valid=None,
                       all_logits: bool = False):
        """Append a (right-padded) token chunk to an existing MLA latent (or
        GQA) cache — the continuous-batching admission path. Pad positions
        never influence valid tokens (mask-invalid and overwritten by the
        next write); routed experts see pad tokens but their outputs are
        sliced away. Returns logits for the last valid token, (B, 1, V) —
        or for every chunk position, (B, S, V), when ``all_logits`` is set
        (the speculative-decode verify pass)."""
        x = L.embed_apply(params["embed"], batch["tokens"])
        b, s = batch["tokens"].shape
        nv = jnp.asarray(s if n_valid is None else n_valid, jnp.int32)
        bt = batch.get("block_table")
        new_dense = []
        for dp, dc in zip(params["dense_layers"], cache["dense_layers"]):
            if bt is not None:
                dc = dict(dc, block_table=bt)
            x, nc = self._block(dp, x, ccfg, dc, "extend", moe=False, n_valid=nv)
            new_dense.append(nc)

        def body(x, scanned):
            lp, c = scanned
            if bt is not None:
                c = dict(c, block_table=bt)
            y, nc = self._block(lp, x, ccfg, c, "extend", moe=True, n_valid=nv)
            return y, nc

        x, new_caches = lax.scan(body, x, (params["layers"], cache["layers"]))
        logits = self._head(params, x if all_logits else take_last_valid(x, nv), ccfg)
        return logits, {"dense_layers": new_dense, "layers": new_caches}

    # --------------------------------------------------- speculative decode
    def spec_verify(self, params, batch, cache, ccfg):
        """Score a (B, 1+K) draft chunk in ONE extend pass (drop-free expert
        dispatch keeps per-token routing independent of the draft batch):
        per-position logits, advanced cache, and the overwritten MLA-latent
        (or GQA KV) rows as the rewind checkpoint."""
        s = batch["tokens"].shape[1]
        bt = batch.get("block_table")
        if bt is not None:
            ckpt = {"dense_layers": [paged_rows_snapshot(c, bt, s)
                                     for c in cache["dense_layers"]],
                    "layers": paged_rows_snapshot(cache["layers"], bt, s),
                    "block_table": bt}
        else:
            ckpt = {"dense_layers": [seq_rows_snapshot(c, s)
                                     for c in cache["dense_layers"]],
                    "layers": seq_rows_snapshot(cache["layers"], s)}
        logits, cache = self.prefill_extend(params, batch, cache, ccfg,
                                            all_logits=True)
        return logits, cache, ckpt

    def spec_rewind(self, cache, ckpt, keep):
        """Per-slot rewind: restore rejected latent/KV rows, rewind pos."""
        bt = ckpt.get("block_table")
        if bt is not None:
            return {"dense_layers": [paged_rows_restore(c, k, bt, keep) for c, k in
                                     zip(cache["dense_layers"], ckpt["dense_layers"])],
                    "layers": paged_rows_restore(cache["layers"], ckpt["layers"], bt, keep)}
        return {"dense_layers": [seq_rows_restore(c, k, keep) for c, k in
                                 zip(cache["dense_layers"], ckpt["dense_layers"])],
                "layers": seq_rows_restore(cache["layers"], ckpt["layers"], keep)}
