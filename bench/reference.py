"""The plain reference: a float32 forward pass of the Qwen1.5 / Qwen2 block,
written from the published description and imports nothing of the program.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) ; q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)            (rotate-half, theta)
                x = x + softmax(q k^T / sqrt(D), causal) v Wo   (GQA: each
                    group of H / Hkv query heads shares one K/V head)
                h = rmsnorm(x) ; x = x + (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) Wlm

Weights are the benchmark's own packed FP4 arrays (``bench.weights``),
decoded here from the E2M1 bit fields times the per-column scale.
Every matmul runs at ``precision="highest"``, one sequence at a time, with
the layers under ``lax.scan`` so that one layer's decoded weights are held
at a time.

``lowp=True`` is the control: the same pass with every matmul input and the
attention probabilities rounded to float8 (E4M3), the nearest precision
below the bfloat16 the configuration serves in. ``lowp_picks`` puts its
choices in the program's place for the check (``bench/control.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def e2m1(code: jax.Array) -> jax.Array:
    """E2M1 code (0..15) -> value: bit 3 sign, bits 2..1 exponent e, bit 0
    mantissa m; e = 0 is subnormal (0 or 0.5), else (1 + m/2) * 2**(e-1)."""
    code = code.astype(jnp.int32)
    e, m = (code >> 1) & 3, (code & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m,
                    (1.0 + 0.5 * m) * jnp.where(e == 1, 1.0, jnp.where(e == 2, 2.0, 4.0)))
    return jnp.where(code >= 8, -mag, mag)


def decode_fp4(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """(K/2, N) bytes, two codes each (low nibble = even row), and (G, N)
    scales -> (K, N) float32 weight."""
    lo, hi = e2m1(codes & 0xF), e2m1(codes >> 4)
    w = jnp.stack([lo, hi], axis=1).reshape(2 * codes.shape[0], codes.shape[1])
    g = scale.shape[0]
    return (w.reshape(g, -1, w.shape[1]) * scale[:, None, :]).reshape(w.shape)


def _round(x, lowp: bool):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if lowp else x


def _linear(p: dict, x, lowp: bool):
    y = jnp.dot(_round(x, lowp), decode_fp4(p["codes"], p["scale"]),
                precision="highest")
    return y + p["b"] if "b" in p else y


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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
        y = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _rope(_linear(a["wq"], y, lowp).reshape(t, h, d), pos, theta)
        k = _rope(_linear(a["wk"], y, lowp).reshape(t, hk, d), pos, theta)
        v = _linear(a["wv"], y, lowp).reshape(t, hk, d)
        k, v = jnp.repeat(k, h // hk, axis=1), jnp.repeat(v, h // hk, axis=1)
        s = jnp.einsum("qhd,khd->hqk", _round(q, lowp), _round(k, lowp),
                       precision="highest") / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _round(p, lowp), _round(v, lowp),
                       precision="highest").reshape(t, h * d)
        x = x + _linear(a["wo"], o, lowp)
        m = lp["mlp"]
        y = _rmsnorm(x, lp["ln2"]["scale"], eps)
        g = _linear(m["w_gate"], y, lowp)
        u = _linear(m["w_up"], y, lowp)
        return x + _linear(m["w_down"], jax.nn.silu(g) * u, lowp), None

    x, _ = lax.scan(layer, x, params["layers"])
    return _linear(params["lm_head"], _rmsnorm(x, params["final_norm"]["scale"], eps),
                   lowp)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _gaps(params, tokens, pos, chosen, cfg_items):
    """Per checked position: the reference's best logit minus the logit of
    the served token."""
    logits = forward(params, tokens, dict(cfg_items))[pos]
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, chosen[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _lowp_picks(params, tokens, pos, cfg_items):
    """Per checked position: the token the float8 pass puts first."""
    return jnp.argmax(forward(params, tokens, dict(cfg_items), lowp=True)[pos], axis=-1)


def _cfg_items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def _sequence(prompt, served, length: int):
    """Prompt + served tokens and, per served token, the position whose
    logits chose it and the token; all padded to ``length`` so that one
    compiled pass serves every request. Padding sits after every checked
    position, so causal attention keeps it out."""
    n = len(served)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served, np.int32)])
    assert len(full) <= length, (len(full), length)
    seq, pos, tok = (np.zeros(length, np.int32) for _ in range(3))
    seq[: len(full)] = full
    pos[:n] = len(prompt) - 1 + np.arange(n)
    tok[:n] = served
    return jnp.asarray(seq), jnp.asarray(pos), jnp.asarray(tok), n


def served_gaps(params, cfg: dict, prompt, served, length: int,
                chosen=None) -> np.ndarray:
    """Gap of every served token of one request, reference float32; with
    ``chosen``, the gap of those tokens at the served positions instead."""
    seq, pos, tok, n = _sequence(prompt, served, length)
    if chosen is not None:
        tok = tok.at[:n].set(jnp.asarray(chosen, jnp.int32))
    return np.asarray(_gaps(params, seq, pos, tok, _cfg_items(cfg)))[:n]


def lowp_picks(params, cfg: dict, prompt, served, length: int) -> np.ndarray:
    """The control's tokens: at every served position of one request, with
    the served tokens before it, the token the float8 pass puts first."""
    seq, pos, _, n = _sequence(prompt, served, length)
    return np.asarray(_lowp_picks(params, seq, pos, _cfg_items(cfg)))[:n]
