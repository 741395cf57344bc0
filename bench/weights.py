"""Seeded weights in the format they are served in, drawn on the device.

The benchmark, not the program, makes the weights: the plain reference may
take nothing the program has made. The tree's layout (leaf names, shapes,
dtypes) comes from ``jax.eval_shape`` of the model's own initializer; every
value is drawn here, in one jitted call, already placed where the engine
serves it from:

* ``codes`` (packed FP4, two E2M1 codes a byte): uniform random bytes, so
  each nibble is any of the 16 codes;
* the ``scale`` beside them (f32, per output column): ``1 / (rms * sqrt(K))``
  with a 10% log-normal spread, where ``rms`` is the root mean square of the
  16 code values, so that a linear keeps unit variance;
* ``b`` (bias, f32): normal, standard deviation 0.1;
* a norm's ``scale`` (f32): ``1 + 0.1 * normal``;
* ``table`` (the embedding, bf16): standard normal.

A leaf none of these covers is drawn by the model family's own ``fill``
(``bench/families/<model_type>.py``), where it has one, or raises.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import seed_sequence

#: root mean square of the 16 E2M1 code values (+-{0, .5, 1, 1.5, 2, 3, 4, 6})
FP4_RMS = math.sqrt(sum(v * v for v in (0, .5, 1, 1.5, 2, 3, 4, 6)) / 8)


def jax_key(seed: int, tag: int = 0):
    """A JAX key from any non-negative whole number seed."""
    word = seed_sequence(seed, tag).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(word)


def _keys(path) -> list:
    return [getattr(p, "key", getattr(p, "idx", None)) for p in path]


def _fill(names: list, sds, key, k_in: int | None, rule=None):
    """One leaf; ``k_in`` is the input width when the leaf is a linear's
    ``scale`` (it sits beside ``codes``). ``rule`` is the family's ``fill``
    for leaves the generic rules do not cover."""
    leaf = names[-1]
    shape, dtype = sds.shape, sds.dtype
    if leaf == "codes":
        return jax.random.bits(key, shape, jnp.uint8)
    if leaf == "scale" and k_in is not None:
        spread = jnp.exp(0.1 * jax.random.normal(key, shape, jnp.float32))
        return (spread / (FP4_RMS * math.sqrt(k_in))).astype(dtype)
    if leaf == "b":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf == "table":
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    drawn = rule(names, sds, key, k_in) if rule is not None else None
    if drawn is not None:
        return drawn
    raise ValueError(f"no rule to draw the weight leaf {'/'.join(map(str, names))}")


def make_params(model, ccfg, seed: int, shardings_fn=None, fill=None):
    """The model's serving params, drawn from ``seed`` in one jitted call.

    ``shardings_fn(abstract_tree)`` gives the ``out_shardings`` (a mesh
    engine's placement), or ``None`` for one device. ``fill`` is the model
    family's rule for leaves the generic rules do not cover."""
    abstract = jax.eval_shape(lambda k: model.init_params(k, ccfg),
                              jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_keys(p) for p, _ in flat]
    code_k = {tuple(n[:-1]): 2 * s.shape[-2] for n, (_, s) in zip(names, flat)
              if n[-1] == "codes"}

    def build(key):
        out = [_fill(n, sds, jax.random.fold_in(key, i), code_k.get(tuple(n[:-1])),
                     fill)
               for i, (n, (_, sds)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(treedef, out)

    shardings = shardings_fn(abstract) if shardings_fn is not None else None
    return jax.jit(build, out_shardings=shardings)(jax_key(seed, 2))
