"""DeepSeek-V2's mechanisms in the program, at CPU sizes: the published
router (softmax, group-limited greedy, x16 without renormalisation), YaRN
rope, the expert layer that holds a share of the experts, the defaults that
leave OLMoE and the dense models as they were, and the served path (chunked
prefill, slot write, decode through the latent cache) against the plain
reference of ``bench/families/deepseek_v2.py``."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.cascade import CascadeConfig
from repro.models import layers as L
from repro.models import moe, registry

F32 = CascadeConfig(mode="train", compute_dtype=jnp.float32)


def published():
    return registry.get_config("deepseek-v2-236b")


# ------------------------------------------------------------------ routing

def numpy_route(p: np.ndarray, n_group: int, topk_group: int, k: int, scale: float):
    """The published ``group_limited_greedy`` on softmax scores ``p`` (T, E),
    token by token: groups ranked by their best score, the ``topk_group``
    best kept, the top ``k`` scores among their experts, times ``scale``."""
    t, e = p.shape
    size = e // n_group
    gates, idx = np.zeros((t, k)), np.zeros((t, k), np.int64)
    for i in range(t):
        best = p[i].reshape(n_group, size).max(axis=1)
        kept = np.argsort(-best, kind="stable")[:topk_group]
        masked = np.where(np.isin(np.arange(e) // size, kept), p[i], 0.0)
        top = np.argsort(-masked, kind="stable")[:k]
        gates[i], idx[i] = masked[top] * scale, top
    return gates, idx


def test_route_is_group_limited_greedy_times_16():
    cfg = published()
    assert (cfg.router_width, cfg.moe_groups, cfg.moe_topk_groups, cfg.moe_top_k,
            cfg.moe_scale, cfg.moe_renorm) == (160, 8, 3, 6, 16.0, False)
    key_x, key_w = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(key_x, (64, 32), jnp.float32)
    w = jax.random.normal(key_w, (32, 160), jnp.float32) / math.sqrt(32)
    gates, idx = moe.route(x, w, cfg)
    p = np.asarray(jax.nn.softmax(jnp.dot(x, w), axis=-1), np.float64)
    want_g, want_i = numpy_route(p, 8, 3, 6, 16.0)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1), np.sort(want_i, 1))
    np.testing.assert_allclose(np.sort(np.asarray(gates), 1), np.sort(want_g, 1),
                               rtol=1e-6)


def test_a_top6_expert_in_a_dropped_group_is_not_taken():
    """Expert 60 has the fifth best score, but its group's best is only the
    fourth best group: the plain top-6 takes it, group-limited greedy (3 of
    8 groups) does not, and takes the next of a kept group instead."""
    cfg = dataclasses.replace(published(), d_model=160)
    logits = np.full(160, -4.0)
    logits[[0, 1, 2, 3]] = [5.0, 4.9, 4.0, 3.5]      # group 0
    logits[20], logits[40], logits[60] = 4.8, 4.7, 4.6   # groups 1, 2, 3
    x = jnp.asarray(logits, jnp.float32)[None, :]
    gates, idx = moe.route(x, jnp.eye(160, dtype=jnp.float32), cfg)
    p = np.asarray(jax.nn.softmax(x, axis=-1), np.float64)
    assert set(np.argsort(-p[0])[:6]) == {0, 1, 2, 20, 40, 60}
    want_g, want_i = numpy_route(p, 8, 3, 6, 16.0)
    assert set(want_i[0]) == set(np.asarray(idx)[0]) == {0, 1, 2, 3, 20, 40}
    np.testing.assert_allclose(np.sort(np.asarray(gates)[0]), np.sort(want_g[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates)[0], 16 * p[0][np.asarray(idx)[0]],
                               rtol=1e-6)         # x16, not renormalised to 1


# --------------------------------------------------------------------- YaRN

def test_yarn_at_the_published_values():
    cfg = published()
    assert L.yarn_range(64, 1e4, 4096, 32, 1) == (10, 23)
    inv = np.asarray(L.yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0), np.float64)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)    # kept
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)  # interpolated
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert moe.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m ** 2, rel=1e-12)
    got_inv, mscale = moe.mla_rope(cfg)
    np.testing.assert_array_equal(np.asarray(got_inv), np.asarray(inv, np.float32))
    assert mscale == 1.0                              # mscale == mscale_all_dim


# ------------------------------------------------------------ held experts

def test_held_shares_add_up_to_the_whole_layer():
    """The routed parts of four disjoint held shares, with the shared
    experts counted once, equal the layer with every expert held."""
    cfg = dataclasses.replace(registry.get_config("deepseek-v2-236b", smoke=True),
                              n_experts=16, moe_groups=4, moe_topk_groups=2, moe_top_k=3)
    whole = moe.moe_ffn_init(jax.random.PRNGKey(1), cfg, F32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model), jnp.float32)
    want = moe.moe_ffn_apply(whole, x, cfg, F32, no_drop=True)
    shared = L.mlp_apply(whole["shared"], x.reshape(-1, cfg.d_model), "swiglu", F32)
    total = shared.reshape(x.shape)
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, n_experts=4, n_routed_experts=16,
                                    first_held_expert=first)
        part = {k: (v if k in ("router", "shared") else
                    jax.tree.map(lambda a: a[first:first + 4], v))
                for k, v in whole.items()}
        y, routed = moe.moe_ffn_apply(part, x, share, F32, no_drop=True, routes=True)
        total = total + (y - shared.reshape(x.shape))
        _, idx = moe.route(x.reshape(-1, cfg.d_model), whole["router"], cfg)
        held = (np.asarray(idx)[:, :, None] == first + np.arange(4)).any(axis=1)
        np.testing.assert_array_equal(np.asarray(routed), held)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


# ------------------------------------------------- the defaults change nothing

def old_route(xf, router, cfg):
    """Routing before group limits, scaling and held shares existed."""
    probs = jax.nn.softmax(jnp.dot(xf.astype(jnp.float32), router), axis=-1)
    gates, idx = lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_renorm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx


def old_apply_rope(x, positions, inv_freq):
    rot2 = inv_freq.shape[0]
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x_rot, x_pass = x[..., : 2 * rot2], x[..., 2 * rot2:]
    x1, x2 = x_rot[..., :rot2], x_rot[..., rot2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def _logits(arch, renorm=None):
    cfg, model = registry.load(arch, smoke=True)
    if renorm is not None:
        cfg = dataclasses.replace(cfg, moe_renorm=renorm)
        model = registry.build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3), F32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, cfg.vocab)
    return np.asarray(model.forward(params, {"tokens": toks}, F32))


@pytest.mark.parametrize("arch,renorm", [("olmoe-1b-7b", None), ("olmoe-1b-7b", True),
                                         ("codeqwen1.5-7b", None), ("qwen2.5-32b", None)],
                         ids=["olmoe", "olmoe_renorm", "codeqwen", "qwen32b"])
def test_new_defaults_leave_other_models_bit_identical(monkeypatch, arch, renorm):
    new = _logits(arch, renorm)
    monkeypatch.setattr(moe, "route", old_route)
    monkeypatch.setattr(L, "apply_rope", old_apply_rope)
    np.testing.assert_array_equal(new, _logits(arch, renorm))


# ------------------------------------------ the served path vs the reference

def deepseek_cell(max_batch: int, max_len: int, **serve):
    """The configuration ``bench/configs/deepseek-v2-ep20.json`` as a one-chip
    cell with this slot grid and ``serve`` laid over its own."""
    import json
    from bench import cells as C

    config = json.loads((C.BENCH_DIR / "configs" / "deepseek-v2-ep20.json").read_text())
    config = dict(config, serve=dict(config["serve"], **serve))
    return C.Cell(name="deepseek-v2-ep20", chips=1, config_name="deepseek-v2-ep20",
                  traffic_name="", config=config, traffic={},
                  workload={"max_batch": max_batch, "max_len": max_len}, metrics={})


def test_served_path_matches_the_family_reference():
    """Chunked prefill (two extend chunks, fused kernels in interpret mode),
    the slot write, then decode through the latent cache, teacher forced:
    every position's logits against the float32 reference's full pass, on
    the benchmark's own weights."""
    from bench import reference, serving

    cell = deepseek_cell(2, 320, compute_dtype="float32", kv_dtype="float32")
    cfg = serving.model_config(cell, dict(cell.config["test_size"], num_attention_heads=4,
                                          num_key_value_heads=4, num_hidden_layers=3))
    engine, params = serving.build_engine(cell, cfg, 2 ** 31 + 5, jax.devices()[:1])
    assert engine.fused and not engine.downgrades
    rng = np.random.default_rng(0)
    n_prompt, n_new = 300, 12
    tokens = rng.integers(0, cfg["vocab_size"], n_prompt + n_new).astype(np.int32)
    t = engine.cache["layers"]["c_kv"].shape[2]
    chunk = engine.scfg.prefill_chunk
    staging = engine.model.init_cache(1, t, dtype=jnp.float32)
    for a in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - a)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = tokens[a:a + n]
        logits, staging = engine.call_step("extend", engine.params, jnp.asarray(toks),
                                           staging, jnp.int32(n))
    got = [np.asarray(logits[0, -1])]
    cache = engine.call_step("write", engine.cache, staging, jnp.int32(0))
    for tok in tokens[n_prompt:-1]:
        grid = np.zeros((engine.scfg.max_batch, 1), np.int32)
        grid[0, 0] = tok
        logits, cache, routed = engine.call_step("decode", engine.params,
                                                 jnp.asarray(grid), cache)
        got.append(np.asarray(logits[0, -1]))
    assert routed.shape == (2, 2, cfg["n_routed_experts"])
    ref = np.asarray(reference.forward(params, jnp.asarray(tokens), cfg))
    ref = ref[n_prompt - 1: n_prompt + n_new - 1]
    np.testing.assert_allclose(np.stack(got), ref, atol=2e-3, rtol=0)
    assert np.abs(ref).max() > 1.0


def test_long_answers_through_a_reused_slot_match_the_reference():
    """Past 1,000 positions, and through a slot whose earlier occupant left
    its latent rows above the new request's: in float32 every token the
    engine serves (fused kernels in interpret mode) is the reference's own
    best, at every position. A cache row read from the wrong slot or
    position, a stale row let through the mask, or rope off at a long
    position would each move some of them."""
    from bench import reference, serving
    from repro.serve.engine import Request

    cell = deepseek_cell(2, 1280, compute_dtype="float32", kv_dtype="float32")
    cfg = serving.model_config(cell, cell.config["test_size"])
    engine, params = serving.build_engine(cell, cfg, 2 ** 31 + 77, jax.devices()[:1])
    rng = np.random.default_rng(1)

    def prompt(n):
        return rng.integers(0, cfg["vocab_size"], n, dtype=np.int32)

    reqs = [Request(uid=0, prompt=prompt(500), max_new_tokens=700),    # rows 0-1199
            Request(uid=1, prompt=prompt(100), max_new_tokens=1150),   # to position 1249
            Request(uid=2, prompt=prompt(200), max_new_tokens=900)]    # uid 0's slot
    for r in reqs:
        engine.submit(r)
    slots = {}
    while engine.busy():
        engine.step()
        for i, s in enumerate(engine.slots):
            if s is not None:
                slots.setdefault(s.uid, i)
    assert slots == {0: 0, 1: 1, 2: 0}
    for r in reqs:
        assert len(r.tokens_out) == r.max_new_tokens
        gaps = reference.served_gaps(params, cfg, r.prompt, r.tokens_out, 1280)
        assert gaps.max() < 1e-3, (r.uid, np.flatnonzero(gaps >= 1e-3) + len(r.prompt) - 1)


def test_the_expert_parallel_path_refuses_a_held_share_and_routes():
    """The shard_map path holds every expert the router scores and returns
    no routes: asked for either, it refuses instead of answering wrong."""
    from repro.distributed.sharding import clear_activation_policy, set_activation_policy
    from repro.launch.mesh import make_mesh

    cfg = registry.get_config("deepseek-v2-236b", smoke=True)
    share = dataclasses.replace(cfg, n_experts=4, n_routed_experts=8)
    u = jnp.zeros((1, 2, cfg.d_model), jnp.float32)
    set_activation_policy(make_mesh((1, 1), ("data", "model")), moe_ep=True)
    try:
        for c, routes in ((cfg, True), (share, False)):
            model = registry.build_model(c)
            lp = moe.moe_ffn_init(jax.random.PRNGKey(0), c, F32)
            with pytest.raises(NotImplementedError, match="expert-parallel"):
                model._moe_ffn(lp, u, F32, no_drop=True, routes=routes)
    finally:
        clear_activation_policy()


# ------------------------------------------------- the routed-work counter

def _engine(arch):
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg, model = registry.load(arch, smoke=True)
    ccfg = CascadeConfig(mode="serve_fp4", compute_dtype=jnp.float32)
    params = registry.init_params(model, ccfg, 0)
    return cfg, ServeEngine(model, params, ccfg, ServeConfig(
        max_batch=4, max_len=64, prefill_chunk=16, fused=True))


def test_a_dense_decode_step_returns_only_its_logits_and_cache():
    cfg, eng = _engine("codeqwen1.5-7b")
    out = eng.call_step("decode", *eng._step_example_args("decode"))
    assert len(out) == 2
    logits, cache = out
    assert logits.shape == (4, 1, cfg.vocab)
    assert jax.tree.structure(cache) == jax.tree.structure(eng.cache)


def test_the_engine_counts_routed_work_of_active_rows():
    from repro.serve import telemetry
    from repro.serve.engine import Request
    cfg, eng = _engine("deepseek-v2-236b")
    rng = np.random.default_rng(0)
    for i in range(3):                                   # 3 of the 4 slots
        eng.submit(Request(uid=i, max_new_tokens=4,
                           prompt=rng.integers(0, cfg.vocab, 9, dtype=np.int32)))
    while eng.busy():
        eng.step()
    c = eng.telemetry.snapshot()[telemetry.ROUTED]
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    assert c["steps"] == 3 and c["rows"] == 9          # 3 decode steps, 3 rows each
    assert c["assignments"] <= c["rows"] * moe_layers * cfg.moe_top_k
    assert c["assignments"] > 0 and 0 < c["pairs"] <= c["steps"] * moe_layers * cfg.n_experts
    assert c["layer_experts"] == c["steps"] * moe_layers * cfg.n_experts
    _, dense = _engine("codeqwen1.5-7b")
    dense.submit(Request(uid=0, max_new_tokens=3, prompt=np.arange(5, dtype=np.int32)))
    while dense.busy():
        dense.step()
    assert telemetry.ROUTED not in dense.telemetry.snapshot()
