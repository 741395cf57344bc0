"""The DeepSeek-V2 block (``model_type`` ``deepseek_v2``): multi-head latent
attention (MLA) with YaRN rope, a leading dense SwiGLU layer, then layers of
routed and shared SwiGLU experts, RMSNorm before each sublayer, and an
untied output head. The configuration runs one chip's share of an
expert-parallel deployment: the router scores every published expert
(``n_routed_experts_published``), the chip holds ``n_routed_experts`` of them
from ``first_held_expert``, and what the absent experts would add is left
out, in the program and in the reference alike.

The reference's pass, written from the published description
(arXiv:2405.04434 and the model's ``modeling_deepseek.py``):

    x = embed[tokens]
    per layer:  h = rmsnorm(x)
                q = rmsnorm(h Wq_a) Wq_b              -> per head (nope 128 | rope 64)
                c, k_pe = split(h Wkv_a) ; c = rmsnorm(c)   (latent 512 | rope 64)
                k_nope, v = split(c Wkv_b)            -> per head (nope 128 | v 128)
                q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)   (k_pe shared by heads)
                x = x + softmax(s * [q_nope q_pe].[k_nope k_pe], causal) v Wo,
                    s = (nope + rope)^-0.5 * m(factor, mscale_all_dim)^2
                h = rmsnorm(x)
                dense layer:  x = x + (silu(h Wg) * (h Wu)) Wd
                expert layer: p = softmax(h W_router) over every expert;
                              group-limited greedy: a group's score is its
                              best p, the topk_group best groups stay, the
                              top num_experts_per_tok of their p are taken;
                              gate = routed_scaling_factor * p (renormalised
                              to sum 1 instead where norm_topk_prob);
                              x = x + sum over the held experts the token went
                              to of gate * expert(h) + shared(h)
    logits = rmsnorm(x) Wlm

with ``yarn_rope`` rotate-half at ``inv_freq`` from YaRN (factor, original
context, beta_fast, beta_slow) and cos and sin times ``m(factor, mscale) /
m(factor, mscale_all_dim)``, ``m(s, a) = 0.1 a ln s + 1``.

Departures, each forced by the program's layout and taken alike by both:

* rope layout: the published code de-interleaves the 64 rope dims (even
  then odd) before rotate-half; with random weights that is a fixed
  permutation of the rope columns of ``wq_b`` and ``wkv_a``, so the
  reference applies rotate-half to the program's column order;
* held experts: only the held experts' part is computed (``[0, 8)`` in the
  benchmark's configuration), as the program's one chip computes it.

Attention runs in blocks of queries so that a 5,120-token check fits (the
whole score tensor would be 13 GB a layer); the expanded per-head form, not
the program's absorbed one.

The counts read the engine's routed-work counter (``serve.routed``, through
``bench.phases.window_counters``) for the held experts' work: weights once
for each (MoE layer, held expert) pair that got tokens in a step, operations
for each token -> held-expert assignment. Without a run, or where the
program counts nothing, they take the expectation under uniform routing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.counts import BF16, FP4_BYTES, SCALE_BYTES, Work, linear_work
from bench.reference import linear, rmsnorm, round_lowp

#: the flat copies of ``rope_scaling`` the reference reads (it takes only
#: hashable configuration values); ``model`` checks that they agree
YARN_KEYS = {"yarn_factor": "factor",
             "yarn_original_max_position_embeddings": "original_max_position_embeddings",
             "yarn_beta_fast": "beta_fast", "yarn_beta_slow": "beta_slow",
             "yarn_mscale": "mscale", "yarn_mscale_all_dim": "mscale_all_dim"}

# ------------------------------------------------------ the model as run


def model(name: str, cfg: dict):
    """The program's ``MoELM`` for ``cfg``, holding its share of the experts."""
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model

    checks = {"hidden_act": "silu", "tie_word_embeddings": False,
              "attention_bias": False, "scoring_func": "softmax",
              "moe_layer_freq": 1, "rms_norm_eps": 1e-6}
    for k, want in checks.items():
        if cfg.get(k) != want:
            raise ValueError(f"{k} {cfg.get(k)!r}: the program runs only {want!r}")
    if cfg["topk_method"] not in ("greedy", "group_limited_greedy"):
        raise ValueError(f"topk_method {cfg['topk_method']!r} is not described")
    rs = cfg.get("rope_scaling")
    if rs is None or rs.get("type") != "yarn":
        raise ValueError("only YaRN rope scaling is described")
    for flat, key in YARN_KEYS.items():
        if cfg[flat] != rs[key]:
            raise ValueError(f"{flat} {cfg[flat]!r} != rope_scaling[{key!r}] {rs[key]!r}")
    held, width = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    if not 0 <= cfg["first_held_expert"] <= width - held:
        raise ValueError("the held experts lie outside the router's")
    grouped = cfg["topk_method"] == "group_limited_greedy"
    fields = dict(
        name=name, family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        n_experts=held, n_routed_experts=width,
        first_held_expert=cfg["first_held_expert"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        moe_renorm=cfg["norm_topk_prob"], moe_scale=cfg["routed_scaling_factor"],
        moe_groups=cfg["n_group"] if grouped else 1,
        moe_topk_groups=cfg["topk_group"] if grouped else 1,
        kv_lora=cfg["kv_lora_rank"], q_lora=cfg["q_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"], qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        yarn_factor=rs["factor"],
        yarn_original_max=rs["original_max_position_embeddings"],
        yarn_beta_fast=rs["beta_fast"], yarn_beta_slow=rs["beta_slow"],
        yarn_mscale=rs["mscale"], yarn_mscale_all_dim=rs["mscale_all_dim"])
    missing = sorted(set(fields) - {f.name for f in dataclasses.fields(ArchConfig)})
    if missing:
        raise ValueError(f"the program's ArchConfig has no {missing}: it cannot run "
                         "this configuration (routing, held experts or YaRN)")
    return build_model(ArchConfig(**fields))


def fill(names, sds, key, k_in):
    """The router (f32, (layers, hidden, experts)): normal, standard
    deviation ``1 / sqrt(hidden)``, so its logits have unit variance."""
    if names[-1] != "router":
        return None
    return jax.random.normal(key, sds.shape, jnp.float32) / math.sqrt(sds.shape[-2])


# -------------------------------------------------------- the reference

REFERENCE_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
                  "n_routed_experts", "n_routed_experts_published",
                  "first_held_expert", "num_experts_per_tok", "topk_method",
                  "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
                  *YARN_KEYS)

#: queries per block of the reference's attention
QUERY_BLOCK = 256


def yarn_m(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg: dict):
    """``(inv_freq, cos/sin factor, softmax scale)`` of the rope head, from
    the published ``DeepseekV2YarnRotaryEmbedding``, in numpy."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    f, orig = cfg["yarn_factor"], cfg["yarn_original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(cfg["yarn_beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / f * ramp + extra * (1 - ramp)
    m = yarn_m(f, cfg["yarn_mscale"]) / yarn_m(f, cfg["yarn_mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if cfg["yarn_mscale_all_dim"]:
        scale *= yarn_m(f, cfg["yarn_mscale_all_dim"]) ** 2
    return inv.astype(np.float32), m, scale


def route(y, router, cfg: dict, lowp: bool):
    """(T, d) -> (gates, idx), each (T, top_k), global expert ids."""
    p = jax.nn.softmax(jnp.dot(round_lowp(y, lowp), router, precision="highest"), axis=-1)
    t, e = p.shape
    if cfg["topk_method"] == "group_limited_greedy":
        g = cfg["n_group"]
        best = jnp.max(p.reshape(t, g, e // g), axis=-1)
        _, top = lax.top_k(best, cfg["topk_group"])
        mask = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], top].set(True)
        p = jnp.where(jnp.repeat(mask, e // g, axis=1), p, 0.0)
    gates, idx = lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    else:
        gates = gates * cfg["routed_scaling_factor"]
    return gates, idx


def _rope(x, cos, sin):
    """x: (T, H, r); rotate-half."""
    r = x.shape[-1] // 2
    x1, x2 = x[..., :r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_attention(q, k, v, scale, lowp):
    """q, k: (T, H, D); v: (T, H, Dv); in blocks of ``QUERY_BLOCK`` queries."""
    t = q.shape[0]
    blk = min(QUERY_BLOCK, t)
    nb = -(-t // blk)
    qp = jnp.pad(q, ((0, nb * blk - t), (0, 0), (0, 0))).reshape(nb, blk, *q.shape[1:])
    k, v = round_lowp(k, lowp), round_lowp(v, lowp)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", round_lowp(qi, lowp), k,
                       precision="highest") * scale
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(rows[None, :, None] >= jnp.arange(t)[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", round_lowp(p, lowp), v, precision="highest")

    o = lax.map(one, (jnp.arange(nb), qp))
    return o.reshape(nb * blk, *o.shape[2:])[:t]


def _swiglu(m, y, lowp):
    return linear(m["w_down"], jax.nn.silu(linear(m["w_gate"], y, lowp))
                  * linear(m["w_up"], y, lowp), lowp)


def forward(params: dict, tokens: jax.Array, cfg: dict, lowp: bool = False):
    """(T,) token ids -> (T, vocab) float32 logits."""
    h, nope, rope = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"])
    vd, kvl, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    held, first = cfg["n_routed_experts"], cfg["first_held_expert"]
    t = tokens.shape[0]
    inv, msc, scale = yarn(cfg)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * msc, jnp.sin(ang)[:, None, :] * msc
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def attention(a, y):
        q = linear(a["wq_b"], rmsnorm(linear(a["wq_a"], y, lowp), a["q_norm"]["scale"], eps),
                   lowp).reshape(t, h, nope + rope)
        kv = linear(a["wkv_a"], y, lowp)
        c = rmsnorm(kv[:, :kvl], a["kv_norm"]["scale"], eps)
        k_pe = _rope(kv[:, None, kvl:], cos, sin)
        kvb = linear(a["wkv_b"], c, lowp).reshape(t, h, nope + vd)
        qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], axis=-1)
        kf = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))],
                             axis=-1)
        o = _causal_attention(qf, kf, kvb[..., nope:], scale, lowp)
        return linear(a["wo"], o.reshape(t, h * vd), lowp)

    def experts(m, y):
        gates, idx = route(y, m["router"], cfg, lowp)
        # each held expert's gate per token (0 where the token went elsewhere)
        w = jnp.sum(gates[:, :, None] * (idx[:, :, None] == first + jnp.arange(held)),
                    axis=1)

        def one(acc, e):
            wg, wu, wd, we = e
            g = jax.nn.silu(linear(wg, y, lowp)) * linear(wu, y, lowp)
            return acc + we[:, None] * linear(wd, g, lowp), None

        out, _ = lax.scan(one, jnp.zeros_like(y), (m["wg"], m["wu"], m["wd"], w.T))
        return out + _swiglu(m["shared"], y, lowp)

    def block(x, lp, moe):
        x = x + attention(lp["attn"], rmsnorm(x, lp["ln1"]["scale"], eps))
        y = rmsnorm(x, lp["ln2"]["scale"], eps)
        return x + (experts(lp["moe"], y) if moe else _swiglu(lp["mlp"], y, lowp))

    for lp in params["dense_layers"]:
        x = block(x, lp, False)
    x, _ = lax.scan(lambda x, lp: (block(x, lp, True), None), x, params["layers"])
    return linear(params["lm_head"], rmsnorm(x, params["final_norm"]["scale"], eps),
                  lowp)


# ------------------------------------------------------------ the counts

def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _mla_linears(cfg: dict) -> List[Tuple[str, int, int, bool]]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return [("wq_a", d, cfg["q_lora_rank"], False),
            ("wq_b", cfg["q_lora_rank"], h * qk, False),
            ("wkv_a", d, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], False),
            ("wo", h * cfg["v_head_dim"], d, False)]


def _swiglu_linears(d: int, f: int) -> List[Tuple[str, int, int, bool]]:
    return [("w_gate", d, f, False), ("w_up", d, f, False), ("w_down", f, d, False)]


def layer_linears(cfg: dict) -> List[Tuple[str, int, int, bool]]:
    """(name, K, N, has_bias) of every linear of one expert layer that the
    FP4 matmul kernel runs: MLA's projections (not ``wkv_b``, which the
    program dequantizes whole) and the shared experts (not the routed
    ones, which run on the XLA path)."""
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return _mla_linears(cfg) + _swiglu_linears(cfg["hidden_size"], shared)


def linears(cfg: dict) -> List[Tuple[int, int, bool]]:
    """(K, N, has_bias) of every linear the FP4 matmul kernel runs per
    token: the dense layers' MLA and MLP, the expert layers' MLA and shared
    experts, and the output head."""
    d = cfg["hidden_size"]
    dense = _mla_linears(cfg) + _swiglu_linears(d, cfg["intermediate_size"])
    per = ([(k, n, b) for _, k, n, b in dense] * cfg["first_k_dense_replace"]
           + [(k, n, b) for _, k, n, b in layer_linears(cfg)] * _moe_layers(cfg))
    return per + [(d, cfg["vocab_size"], False)]


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent and the rope key of one position in every layer, bf16."""
    return (cfg["num_hidden_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * BF16)


def attention_decode_work(cfg: dict, positions: float) -> Work:
    """One decode step's attention in ONE layer in the absorbed form, summed
    over its active slots, which attend over ``positions`` valid cache rows
    in all: per row, the scores against the latent and the rope key
    (2 H (latent + rope)) and the latent's share of the context (2 H
    latent), and the row's latent and rope key read once."""
    h, kvl, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                    cfg["qk_rope_head_dim"])
    return Work(positions * (2.0 * h * (kvl + rope) + 2.0 * h * kvl),
                positions * (kvl + rope) * BF16)


def _wkv_b(cfg: dict) -> Tuple[int, int]:
    h = cfg["num_attention_heads"]
    return cfg["kv_lora_rank"], h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def _absorption_work(cfg: dict, active: int) -> Work:
    """One layer's absorption products for ``active`` tokens (the query's
    nope part into the latent space, the context out of it) and ``wkv_b``
    read once."""
    h, kvl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    k, n = _wkv_b(cfg)
    flops = 2.0 * active * h * kvl * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    return Work(flops, k * n * FP4_BYTES + n * SCALE_BYTES)


def _router_work(cfg: dict, tokens: int) -> Work:
    d, e = cfg["hidden_size"], cfg["n_routed_experts_published"]
    return Work(2.0 * tokens * d * e, d * e * 4 + tokens * d * BF16)


def _expert_work(cfg: dict, assignments: float, pairs: float) -> Work:
    """Held experts: each (layer, expert) pair that got tokens reads its
    three FP4 matrices once; each assignment runs them on one token."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = sum(k * n * FP4_BYTES + n * SCALE_BYTES for _, k, n, _ in _swiglu_linears(d, f))
    return Work(2.0 * assignments * 3 * d * f,
                pairs * weights + assignments * 3 * (d + f) * BF16)


def _expected_routing(cfg: dict, tokens: int) -> Tuple[float, float]:
    """(assignments, pairs) of ``tokens`` routed at once, every MoE layer,
    under uniform routing: each token picks top_k of the router's experts."""
    held, e = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    k, layers = cfg["num_experts_per_tok"], _moe_layers(cfg)
    return (tokens * layers * k * held / e,
            layers * held * (1.0 - (1.0 - k / e) ** tokens))


def routed(cfg: dict, active: int, run=None) -> Tuple[float, float]:
    """(assignments, pairs) of one decode step of ``active`` rows: from the
    run's routed-work counter (its assignments a row and pairs a step over
    the window) where the program counts them, else the expectation."""
    if run is not None:
        from bench import phases
        w = phases.window_counters(run)
        name = getattr(phases.names(), "ROUTED", None)
        c = (w or {}).get(name) or {}
        if c.get("rows"):
            return active * c["assignments"] / c["rows"], c["pairs"] / c["steps"]
    return _expected_routing(cfg, active)


def decode_step_work(cfg: dict, active: int, positions: float, run=None) -> Work:
    """One decode step of the whole model for ``active`` slots attending over
    ``positions`` valid cache rows in all (per layer): the kernel's linears,
    MLA in its absorbed form in every layer, the router and the held
    experts' routed work."""
    layers = cfg["num_hidden_layers"]
    att = attention_decode_work(cfg, positions) + _absorption_work(cfg, active)
    router = _router_work(cfg, active)
    moe = _moe_layers(cfg)
    return (cascade_matmul_work(cfg, active)
            + Work(att.flops * layers, att.bytes * layers)
            + Work(router.flops * moe, router.bytes * moe)
            + _expert_work(cfg, *routed(cfg, active, run)))


def prefill_work(cfg: dict, prompt: int, chunk: int, run=None) -> Work:
    """Prefill of one prompt in chunks of ``chunk`` tokens: the kernel's
    linears, ``wkv_b`` and the router over every token (weights once per
    chunk), causal attention in the expanded form (each token attends to
    itself and every earlier one: 2 H (nope + rope) + 2 H v a pair), the
    latent and rope key of every token written, and the held experts'
    expected routed work per chunk."""
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    chunks = -(-prompt // chunk)
    k, n = _wkv_b(cfg)
    mats = linears(cfg) + [(k, n, False)] * layers
    flops = (2.0 * sum(k * n for k, n, _ in mats) * prompt
             + layers * 2.0 * h * (qk + cfg["v_head_dim"]) * prompt * (prompt + 1) / 2)
    weights = sum(k * n * FP4_BYTES + n * SCALE_BYTES for k, n, _ in mats)
    w = Work(flops, chunks * weights + prompt * kv_bytes_per_token(cfg))
    router = _router_work(cfg, prompt)
    w = w + Work(router.flops * _moe_layers(cfg), router.bytes * _moe_layers(cfg))
    for c in range(chunks):
        w = w + _expert_work(cfg, *_expected_routing(cfg, min(chunk, prompt - c * chunk)))
    return w


def cascade_matmul_work(cfg: dict, active: int, run=None) -> Work:
    """The FP4 matmul kernel's work in one decode step: every linear it runs
    over the active rows (logical shapes, M x K x N)."""
    w = Work()
    for k, n, b in linears(cfg):
        w = w + linear_work(active, k, n, b)
    return w
