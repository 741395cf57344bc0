"""The plain reference that decides ``correct``: what every model family
shares. It imports nothing of the program.

The model's own float32 pass is its family's ``forward(params, tokens, cfg,
lowp=False)`` (``bench/families/<model_type>.py``), written from the
published description; ``forward`` here dispatches to it by the
configuration's ``model_type``. This module holds the rest:

* the served format: the benchmark's own packed FP4 arrays
  (``bench.weights``), decoded from the E2M1 bit fields times the
  per-column scale, and the FP4 ``linear`` and ``rmsnorm`` a family's pass
  is built from, every matmul at ``precision="highest"``;
* the control: ``lowp=True`` rounds every matmul input and the attention
  probabilities to float8 (E4M3, ``round_lowp``), the nearest precision
  below the bfloat16 the configuration serves in; ``lowp_picks`` puts its
  choices in the program's place for the check (``bench/control.py``);
* the jitted passes that judge served tokens, one sequence at a time, with
  the configuration's ``model_type`` and its family's ``REFERENCE_KEYS``
  as their static configuration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import cells


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


def round_lowp(x, lowp: bool):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if lowp else x


def linear(p: dict, x, lowp: bool):
    y = jnp.dot(round_lowp(x, lowp), decode_fp4(p["codes"], p["scale"]),
                precision="highest")
    return y + p["b"] if "b" in p else y


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def forward(params: dict, tokens: jax.Array, cfg: dict, lowp: bool = False):
    """(T,) token ids -> (T, vocab) float32 logits: the pass of the
    family that ``cfg["model_type"]`` names."""
    return cells.family(cfg["model_type"]).forward(params, tokens, cfg, lowp)


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
    keys = cells.family(cfg["model_type"]).REFERENCE_KEYS
    return (("model_type", cfg["model_type"]),) + tuple((k, cfg[k]) for k in keys)


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
