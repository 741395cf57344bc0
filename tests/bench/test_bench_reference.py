"""The plain reference against the engine's own path (chunked prefill through
the extend closure, the slot write, decode through the cache), on logits,
at a size the CPU holds, for MHA and GQA."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells as C
from bench import reference, serving

#: the cell's serving settings at float32, where the two paths agree closely
F32 = {"compute_dtype": "float32", "kv_dtype": "float32"}


def engine_logits(engine, tokens, n_prompt):
    """Logits of the prompt's last position (extend, then write) and of
    every later position (decode, teacher forced)."""
    t = engine.cache["layers"]["k"].shape[2]
    chunk = engine.scfg.prefill_chunk
    staging = engine.model.init_cache(1, t, dtype=jnp.float32)
    for a in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - a)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = tokens[a:a + n]
        logits, staging = engine.call_step("extend", engine.params,
                                           jnp.asarray(toks), staging, jnp.int32(n))
    out = [np.asarray(logits[0, -1])]
    cache = engine.call_step("write", engine.cache, staging, jnp.int32(0))
    for tok in tokens[n_prompt:-1]:
        grid = np.zeros((engine.scfg.max_batch, 1), np.int32)
        grid[0, 0] = tok
        logits, cache = engine.call_step("decode", engine.params, jnp.asarray(grid), cache)
        out.append(np.asarray(logits[0, -1]))
    return np.stack(out)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_reference_matches_the_served_path(kv_heads):
    cell = C.load_cell("codeqwen7b.decode_saturated")
    cell = dataclasses.replace(cell, config=dict(
        cell.config, serve=dict(cell.config["serve"], **F32)))
    cfg = serving.model_config(cell, dict(cell.config["test_size"],
                                          num_key_value_heads=kv_heads))
    engine, params = serving.build_engine(cell, cfg, 2 ** 31 + 3, jax.devices()[:1])
    rng = np.random.default_rng(0)
    n_prompt, n_new = 300, 12          # two extend chunks, then decode
    tokens = rng.integers(0, cfg["vocab_size"], n_prompt + n_new).astype(np.int32)
    got = engine_logits(engine, tokens, n_prompt)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.forward(params, jnp.asarray(tokens), cfg))
    ref = ref[n_prompt - 1: n_prompt + n_new - 1]
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=2e-3, rtol=0)
    assert np.abs(ref).max() > 1.0          # logits are not trivially small


def test_fp4_decoder_matches_the_e2m1_grid():
    codes = jnp.arange(256, dtype=jnp.uint8).reshape(1, 256)
    w = reference.decode_fp4(codes, jnp.ones((1, 256), jnp.float32))
    grid = [0, .5, 1, 1.5, 2, 3, 4, 6]
    vals = grid + [-v for v in grid]
    np.testing.assert_array_equal(np.asarray(w[0]), [vals[c & 15] for c in range(256)])
    np.testing.assert_array_equal(np.asarray(w[1]), [vals[c >> 4] for c in range(256)])
