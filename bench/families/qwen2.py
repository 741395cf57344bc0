"""The Qwen1.5 / Qwen2 block (``model_type`` ``qwen2``): a dense decoder of
GQA attention with q/k/v biases and a SwiGLU MLP, RMSNorm before each, and
an untied output head.

The reference's pass, written from the published description:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) ; q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)            (rotate-half, theta)
                x = x + softmax(q k^T / sqrt(D), causal) v Wo   (GQA: each
                    group of H / Hkv query heads shares one K/V head)
                h = rmsnorm(x) ; x = x + (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) Wlm

The layers run under ``lax.scan`` so that one layer's decoded weights are
held at a time. The counts follow from the shapes alone: a dense block's
work does not depend on what the program did, so they leave ``run`` unread.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.counts import BF16, FP4_BYTES, SCALE_BYTES, Work, linear_work
from bench.reference import linear, rmsnorm, round_lowp

# ------------------------------------------------------ the model as run

#: configuration-file keys -> the program's ``ArchConfig`` fields
ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "rope_theta": "rope_theta", "attention_bias": "qkv_bias"}


def model(name: str, cfg: dict):
    """The program's ``TransformerLM`` for ``cfg``."""
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model

    if cfg.get("hidden_act") != "silu" or cfg.get("tie_word_embeddings"):
        raise ValueError("only SwiGLU blocks with an untied head are described")
    arch = ArchConfig(name=name, family="dense",
                      **{f: cfg[k] for k, f in ARCH_KEYS.items()})
    return build_model(arch)


# -------------------------------------------------------- the reference

REFERENCE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                  "rms_norm_eps", "rope_theta")


def _rope(x, pos, theta):
    """x: (T, H, D); rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params: dict, tokens: jax.Array, cfg: dict, lowp: bool = False):
    """(T,) token ids -> (T, vocab) float32 logits."""
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        y = rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _rope(linear(a["wq"], y, lowp).reshape(t, h, d), pos, theta)
        k = _rope(linear(a["wk"], y, lowp).reshape(t, hk, d), pos, theta)
        v = linear(a["wv"], y, lowp).reshape(t, hk, d)
        k, v = jnp.repeat(k, h // hk, axis=1), jnp.repeat(v, h // hk, axis=1)
        s = jnp.einsum("qhd,khd->hqk", round_lowp(q, lowp), round_lowp(k, lowp),
                       precision="highest") / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", round_lowp(p, lowp), round_lowp(v, lowp),
                       precision="highest").reshape(t, h * d)
        x = x + linear(a["wo"], o, lowp)
        m = lp["mlp"]
        y = rmsnorm(x, lp["ln2"]["scale"], eps)
        g = linear(m["w_gate"], y, lowp)
        u = linear(m["w_up"], y, lowp)
        return x + linear(m["w_down"], jax.nn.silu(g) * u, lowp), None

    x, _ = lax.scan(layer, x, params["layers"])
    return linear(params["lm_head"], rmsnorm(x, params["final_norm"]["scale"], eps),
                  lowp)


# ------------------------------------------------------------ the counts

def layer_linears(cfg: dict) -> List[Tuple[str, int, int, bool]]:
    """(name, K, N, has_bias) of every linear of one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    bias = bool(cfg.get("attention_bias", False))
    return [("wq", d, h * hd, bias), ("wk", d, hk * hd, bias),
            ("wv", d, hk * hd, bias), ("wo", h * hd, d, False),
            ("w_gate", d, f, False), ("w_up", d, f, False),
            ("w_down", f, d, False)]


def linears(cfg: dict) -> List[Tuple[int, int, bool]]:
    """(K, N, has_bias) of every linear the model runs per token: all
    layers' and the output head."""
    per = [(k, n, b) for _, k, n, b in layer_linears(cfg)]
    return per * cfg["num_hidden_layers"] + [
        (cfg["hidden_size"], cfg["vocab_size"], False)]


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position in every layer, bf16."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def attention_decode_work(cfg: dict, positions: float) -> Work:
    """One decode step's attention in ONE layer, summed over its active
    slots, which attend over ``positions`` valid cache rows in all: q.k and
    p.v (2 * 2 * H * D FLOPs a row) and each valid K/V row read once."""
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return Work(4.0 * positions * h * hd, positions * 2 * hk * hd * BF16)


def decode_step_work(cfg: dict, active: int, positions: float, run=None) -> Work:
    """One decode step of the whole model for ``active`` slots attending over
    ``positions`` valid cache rows in all (per layer): every linear's FP4
    codes and scales, the head included, and the valid K/V rows of every
    layer."""
    att = attention_decode_work(cfg, positions)
    layers = cfg["num_hidden_layers"]
    return cascade_matmul_work(cfg, active) + Work(att.flops * layers,
                                                   att.bytes * layers)


def prefill_work(cfg: dict, prompt: int, chunk: int, run=None) -> Work:
    """Prefill of one prompt in chunks of ``chunk`` tokens: 2 * N * tokens
    plus causal attention (each token attends to itself and every earlier
    one), weights streamed once per chunk, K/V of every token written."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    chunks = -(-prompt // chunk)
    flops = (2.0 * sum(k * n for k, n, _ in linears(cfg)) * prompt
             + layers * 4.0 * h * hd * prompt * (prompt + 1) / 2)
    weights = sum(k * n * FP4_BYTES + n * SCALE_BYTES for k, n, _ in linears(cfg))
    return Work(flops, chunks * weights + prompt * kv_bytes_per_token(cfg))


def cascade_matmul_work(cfg: dict, active: int, run=None) -> Work:
    """The FP4 matmul kernel's work in one decode step: every linear over
    the active rows (logical shapes, M x K x N)."""
    w = Work()
    for k, n, b in linears(cfg):
        w = w + linear_work(active, k, n, b)
    return w
