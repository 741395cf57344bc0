"""Batched continuous-batching engine: parity, invariants, fault injection.

The batched path must be a pure optimization: token-exact against the
slot-wise reference on every schedule (whole-prompt, chunked prefill,
token-budget interleaving) for EVERY registry arch family — full-attention
KV, MLA latent caches, ring-buffer + recurrent state, SSD state — with
admission/retirement behaving as a FIFO slot grid and CREST probes still
confirming injected faults.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cascade import CascadeConfig
from repro.models import registry
from repro.serve.engine import Request, ServeConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

CCFG = CascadeConfig(mode="train", compute_dtype=jnp.float32)


def _load(arch):
    cfg, model = registry.load(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0), CCFG)
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny_model():
    return _load("codeqwen1.5-7b")


@pytest.fixture(scope="module", params=sorted(registry.FAMILY_SMOKE), ids=str)
def family_model(request):
    """One smoke model per serving family (the CI arch-matrix axis)."""
    return (request.param,) + _load(registry.FAMILY_SMOKE[request.param])


def _requests(cfg, lens, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
                    max_new_tokens=max_new) for i, n in enumerate(lens)]


def _run(model, params, cfg, lens, scfg, max_new=4, seed=0, max_steps=400):
    eng = ServeEngine(model, params, CCFG, scfg)
    reqs = _requests(cfg, lens, max_new=max_new, seed=seed)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_steps)
    return reqs, eng


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_batched_equals_slotwise_token_exact(tiny_model):
    """The tentpole guarantee: one jitted batched decode over the slot grid
    produces exactly the tokens of the per-slot reference loop."""
    cfg, model, params = tiny_model
    lens = [8, 5, 12, 8, 3, 20]
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, eng = _run(model, params, cfg, lens,
                    ServeConfig(max_batch=2, max_len=64, batched=True,
                                prefill_chunk=8))
    assert eng.batched
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


def test_chunked_prefill_and_budget_token_exact(tiny_model):
    """Chunked prefill (prompt split across engine steps under a token
    budget) must not change any emitted token."""
    cfg, model, params = tiny_model
    lens = [17, 8, 29, 4]
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=True,
                              prefill_chunk=8, token_budget=8))
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


def test_batched_decode_is_single_dispatch(tiny_model):
    """All active slots decode in ONE decode_step call per engine step."""
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=4, max_len=64, batched=True))
    for r in _requests(cfg, [8, 8, 8, 8]):
        eng.submit(r)
    calls = []
    inner = eng._decode_fn
    eng._decode_fn = lambda *a: calls.append(1) or inner(*a)
    eng.step()
    assert sum(s is not None for s in eng.slots) == 4
    assert len(calls) == 1, "batched step must issue one decode dispatch"


# ---------------------------------------------------------------------------
# per-family parity (transformer / moe / griffin / ssm)
# ---------------------------------------------------------------------------

def test_family_batched_equals_slotwise_token_exact(family_model):
    """Every registry arch family decodes token-exact through the stacked
    grid — MLA latent caches, ring buffers + recurrent state, SSD state."""
    fam, cfg, model, params = family_model
    lens = [2, 8, 5, 12, 20, 3]                 # incl. prompt < conv receptive field
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, eng = _run(model, params, cfg, lens,
                    ServeConfig(max_batch=2, max_len=64, batched=True,
                                prefill_chunk=8))
    assert eng.batched, f"{fam} must run the batched fast path"
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (fam, a.uid, a.tokens_out, b.tokens_out)


def test_family_budgeted_chunked_prefill_token_exact(family_model):
    """Chunked prefill under a per-step token budget (prompts split across
    engine steps, interleaved with decode) stays token-exact per family."""
    fam, cfg, model, params = family_model
    lens = [17, 8, 29, 4]
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=True,
                              prefill_chunk=8, token_budget=8))
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (fam, a.uid, a.tokens_out, b.tokens_out)


def test_family_failover_clone_token_exact(family_model):
    """Replica death mid-decode: the survivor rebuilds decode state — incl.
    recurrent {conv, h}/{conv, ssd} state — from prompt + emitted tokens."""
    from repro.serve.elastic import ReplicaSet
    fam, cfg, model, params = family_model
    ref, _ = _run(model, params, cfg, [8], ServeConfig(max_batch=1, max_len=64),
                  max_new=8, seed=3)
    scfg = ServeConfig(max_batch=1, max_len=64)
    rs = ReplicaSet([ServeEngine(model, params, CCFG, scfg) for _ in range(2)])
    victim = _requests(cfg, [8], max_new=8, seed=3)[0]
    rs.submit(victim)
    for _ in range(3):                         # prefill + a couple of decodes
        rs.step()
    killed_on = next(i for i, e in enumerate(rs.engines) if victim in e.slots)
    rs.kill_replica(killed_on)
    rs.drain(max_steps=200)
    clone = rs.requeued[0]
    assert clone.done
    assert clone.tokens_out == ref[0].tokens_out, (fam, clone.tokens_out,
                                                   ref[0].tokens_out)


def test_moe_parity_under_expert_capacity_pressure():
    """Many concurrent slots routing into few experts: serving dispatch is
    drop-free, so a token's experts never depend on unrelated slot contents
    or chunk boundaries — batched stays token-exact at large batch too
    (with capacity drops, requests diverged at max_batch=12)."""
    cfg, model, params = _load(registry.FAMILY_SMOKE["moe"])
    lens = [8] * 12
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=12, max_len=64, batched=False),
                  max_new=6, max_steps=600)
    out, eng = _run(model, params, cfg, lens,
                    ServeConfig(max_batch=12, max_len=64, batched=True,
                                prefill_chunk=8), max_new=6, max_steps=600)
    assert eng.batched
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


# ---------------------------------------------------------------------------
# ring-buffer edge cases (griffin: windowed attention + recurrent state)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def griffin_w8():
    """Griffin with a tiny window so prompts overrun the ring quickly."""
    cfg, model = registry.load("recurrentgemma-2b", smoke=True)
    cfg = dataclasses.replace(cfg, window=8)
    model = registry.build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), CCFG)
    return cfg, model, params


def test_griffin_prompt_longer_than_window_token_exact(griffin_w8):
    """Prompts several times the attention window chunk-prefill through the
    ring without clobbering in-window entries."""
    cfg, model, params = griffin_w8
    lens = [23, 40, 9]                          # all beyond window=8
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, eng = _run(model, params, cfg, lens,
                    ServeConfig(max_batch=2, max_len=64, batched=True,
                                prefill_chunk=4))
    assert eng.batched
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


def test_griffin_chunk_boundary_on_ring_wrap_token_exact(griffin_w8):
    """Chunk == ring length: every chunk boundary lands exactly on the ring
    wrap (the hardest alignment for the drop-scatter write path)."""
    cfg, model, params = griffin_w8
    lens = [16, 24, 17, 8]                      # multiples of window=8 + one off
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=False))
    out, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=True,
                              prefill_chunk=8))
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


def test_griffin_oversized_chunk_clamped_to_ring(griffin_w8):
    """prefill_chunk larger than the ring is clamped (a chunk must fit the
    ring so within-chunk writes never collide) — still token-exact."""
    cfg, model, params = griffin_w8
    ref, _ = _run(model, params, cfg, [20],
                  ServeConfig(max_batch=1, max_len=64, batched=False))
    out, eng = _run(model, params, cfg, [20],
                    ServeConfig(max_batch=1, max_len=64, batched=True,
                                prefill_chunk=32))
    assert eng._chunk_cap == 8
    assert ref[0].tokens_out == out[0].tokens_out


def test_griffin_window_larger_than_max_len_token_exact():
    """window > max_len: the ring must still hold the FULL window (state is
    O(window), not O(max_len)) — batched chunk-prefill may not silently
    truncate attention relative to the slot-wise whole-prompt baseline."""
    cfg, model = registry.load("recurrentgemma-2b", smoke=True)   # window=16
    params = model.init_params(jax.random.PRNGKey(0), CCFG)
    lens = [20, 30, 10]                          # beyond max_len, around window
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=12, batched=False))
    out, eng = _run(model, params, cfg, lens,
                    ServeConfig(max_batch=2, max_len=12, batched=True,
                                prefill_chunk=8))
    assert eng.batched
    for a, b in zip(ref, out):
        assert a.tokens_out == b.tokens_out, (a.uid, a.tokens_out, b.tokens_out)


def test_window_aware_admission_not_spuriously_rejected(griffin_w8):
    """Windowed/recurrent archs hold O(window) state: prompts longer than
    ``max_len`` must be admitted (and never context-limit retired), while
    full-attention archs still reject them."""
    cfg, model, params = griffin_w8
    for batched in (True, False):
        reqs, eng = _run(model, params, cfg, [30, 70],
                         ServeConfig(max_batch=2, max_len=16, batched=batched,
                                     prefill_chunk=8), max_new=5)
        assert all(r.done and len(r.tokens_out) == 5 for r in reqs), (
            batched, [r.tokens_out for r in reqs])
        assert eng.metrics()["requests_rejected"] == 0

    # ssm likewise has no context limit
    cfg_s, model_s, params_s = _load("mamba2-370m")
    reqs, eng = _run(model_s, params_s, cfg_s, [30],
                     ServeConfig(max_batch=1, max_len=16, batched=True,
                                 prefill_chunk=8), max_new=5)
    assert reqs[0].done and len(reqs[0].tokens_out) == 5
    assert eng.metrics()["requests_rejected"] == 0


# ---------------------------------------------------------------------------
# sampling (temperature / top-k)
# ---------------------------------------------------------------------------

def test_sampling_topk1_matches_greedy_batched_and_slotwise(tiny_model):
    """top_k=1 collapses sampling to argmax: token-exact with the greedy
    default in both engine modes (so sampling never perturbs the fast path)."""
    cfg, model, params = tiny_model
    lens = [8, 5, 12]
    ref, _ = _run(model, params, cfg, lens,
                  ServeConfig(max_batch=2, max_len=64, batched=True,
                              prefill_chunk=8))
    for batched in (True, False):
        out, _ = _run(model, params, cfg, lens,
                      ServeConfig(max_batch=2, max_len=64, batched=batched,
                                  prefill_chunk=8, temperature=0.8, top_k=1))
        for a, b in zip(ref, out):
            assert a.tokens_out == b.tokens_out, (batched, a.uid)


def test_sampling_topk_tied_kth_keeps_all_tied_candidates():
    """The documented top-k tie semantics: the truncated support is
    VALUE-defined — every logit >= the k-th largest survives, so a tie at
    the k-th logit keeps MORE than k candidates (no arbitrary index-order
    tie-break). The spec-sampling verify pass relies on plain decode and
    verify sharing this exact truncation (`_truncate_logits` is the single
    implementation both use)."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import _sample_tokens, _truncate_logits
    # three-way tie AT the k-th (2nd) largest: candidates 1, 2, 3 all tie
    row = jnp.asarray([[4.0, 1.0, 1.0, 1.0, 0.5, -2.0]], jnp.float32)
    x = np.asarray(_truncate_logits(row, 1.0, 2))
    assert np.isfinite(x[0, :4]).all(), x          # max + all tied kth
    assert not np.isfinite(x[0, 4:]).any(), x      # below kth: masked
    # and the sampler actually reaches every tied candidate (never beyond)
    draws = np.asarray(jax.vmap(
        lambda i: _sample_tokens(row, jax.random.fold_in(
            jax.random.PRNGKey(3), i), 1.0, 2)[0])(jnp.arange(800)))
    assert set(np.unique(draws)) <= {0, 1, 2, 3}
    assert {1, 2, 3} <= set(np.unique(draws)), np.unique(draws)
    # consequence (documented): top_k=1 with a TIED max samples among the
    # tied tokens rather than collapsing to first-index argmax
    tied_max = jnp.asarray([[2.0, 2.0, -1.0]], jnp.float32)
    x1 = np.asarray(_truncate_logits(tied_max, 1.0, 1))
    assert np.isfinite(x1[0, :2]).all() and not np.isfinite(x1[0, 2])


def test_sampling_failover_never_rewrites_emitted_tokens(tiny_model):
    """Failover under temperature sampling: the rebuild carries EVERY
    emitted token in the clone's prompt, so a re-draw on the survivor can
    never rewrite history the client already received."""
    from repro.serve.elastic import ReplicaSet
    cfg, model, params = tiny_model
    scfg = ServeConfig(max_batch=1, max_len=64, temperature=1.0, top_k=8,
                       sample_seed=11)
    rs = ReplicaSet([ServeEngine(model, params, CCFG, scfg) for _ in range(2)])
    victim = _requests(cfg, [8], max_new=10, seed=3)[0]
    rs.submit(victim)
    for _ in range(4):                         # prefill + a few decodes
        rs.step()
    emitted = list(victim.tokens_out)
    assert len(emitted) >= 2
    killed_on = next(i for i, e in enumerate(rs.engines) if victim in e.slots)
    rs.kill_replica(killed_on)
    rs.drain(max_steps=200)
    clone = rs.requeued[0]
    assert clone.done and len(clone.tokens_out) == 10
    assert clone.tokens_out[:len(emitted)] == emitted, (
        clone.tokens_out, emitted)


def test_sampling_deterministic_given_seed(tiny_model):
    """Same seed + same schedule => identical samples; tokens stay in-vocab
    and within the top-k support."""
    cfg, model, params = tiny_model
    scfg = ServeConfig(max_batch=2, max_len=64, batched=True, prefill_chunk=8,
                       temperature=1.0, top_k=5, sample_seed=7)
    a, _ = _run(model, params, cfg, [8, 5], scfg, max_new=6)
    b, _ = _run(model, params, cfg, [8, 5], scfg, max_new=6)
    for ra, rb in zip(a, b):
        assert ra.tokens_out == rb.tokens_out
        assert all(0 <= t < cfg.vocab for t in ra.tokens_out)


# ---------------------------------------------------------------------------
# continuous-batching invariants
# ---------------------------------------------------------------------------

def test_fifo_admission_and_slot_reuse(tiny_model):
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=2, max_len=64, batched=True))
    reqs = _requests(cfg, [8] * 5, max_new=3)
    for r in reqs:
        eng.submit(r)
    admitted = []
    for _ in range(100):
        eng.step()
        for s in eng.slots:
            if s is not None and s.uid not in admitted:
                admitted.append(s.uid)
        if not eng.busy():
            break
    assert admitted == [0, 1, 2, 3, 4], "admission must be FIFO"
    assert all(r.done for r in reqs)
    assert all(s is None for s in eng.slots), "retirement must free slots"
    assert not eng.queue


def test_max_new_tokens_retirement_and_queue_drain(tiny_model):
    cfg, model, params = tiny_model
    reqs, eng = _run(model, params, cfg, [8] * 6,
                     ServeConfig(max_batch=3, max_len=64, batched=True),
                     max_new=5)
    assert all(r.done for r in reqs)
    assert all(len(r.tokens_out) == 5 for r in reqs)
    assert not eng.busy()
    m = eng.metrics()
    assert m["requests_finished"] == 6
    assert m["decode_tokens"] == 6 * 4          # first token comes from prefill


def test_eos_retirement_frees_slot_early(tiny_model):
    """Set eos_id to the first decode token of request 0 (learned from a dry
    run): the request must retire early and its slot be reused."""
    cfg, model, params = tiny_model
    probe, _ = _run(model, params, cfg, [8],
                    ServeConfig(max_batch=1, max_len=64, batched=True),
                    max_new=8)
    eos = probe[0].tokens_out[1]                # first *decoded* token
    reqs, eng = _run(model, params, cfg, [8],
                     ServeConfig(max_batch=1, max_len=64, batched=True, eos_id=eos),
                     max_new=8)
    assert reqs[0].done
    assert len(reqs[0].tokens_out) == 2, reqs[0].tokens_out
    assert reqs[0].tokens_out[-1] == eos


def test_budgeted_prefill_interleaves_with_decode(tiny_model):
    """While a long prompt is being chunk-prefilled, an already-resident
    stream must keep producing tokens (bounded decode latency)."""
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=2, max_len=64, batched=True,
                                  prefill_chunk=4, token_budget=4))
    short, long_ = _requests(cfg, [4, 40], max_new=16)
    eng.submit(short)
    eng.step()                                  # short becomes resident
    eng.submit(long_)
    progressed = False
    for _ in range(4):                          # 40-token prompt needs 10 chunks
        before = len(short.tokens_out)
        eng.step()
        if eng._staging is not None and len(short.tokens_out) > before:
            progressed = True
    assert progressed, "decode must advance while a prompt is mid-prefill"
    eng.run_until_drained(200)
    assert short.done and long_.done


def test_evict_and_abort_in_flight(tiny_model):
    cfg, model, params = tiny_model
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=2, max_len=64, batched=True))
    reqs = _requests(cfg, [8, 8, 8], max_new=8)
    for r in reqs:
        eng.submit(r)
    eng.step()
    aborted = eng.abort_in_flight()
    assert {r.uid for r in aborted} == {0, 1}
    assert all(s is None for s in eng.slots)
    assert eng.busy()                           # uid 2 still queued
    eng.run_until_drained(100)
    assert reqs[2].done


def test_failover_clone_continues_token_exact(tiny_model):
    """A request re-queued after replica death must finish with exactly the
    tokens an unkilled run would have produced (greedy decode + idempotent
    regenerate from prompt + emitted prefix)."""
    from repro.serve.elastic import ReplicaSet
    cfg, model, params = tiny_model
    ref, _ = _run(model, params, cfg, [8], ServeConfig(max_batch=1, max_len=64),
                  max_new=8, seed=3)
    scfg = ServeConfig(max_batch=1, max_len=64)
    rs = ReplicaSet([ServeEngine(model, params, CCFG, scfg) for _ in range(2)])
    victim = _requests(cfg, [8], max_new=8, seed=3)[0]
    rs.submit(victim)
    for _ in range(3):                         # prefill + a couple of decodes
        rs.step()
    killed_on = next(i for i, e in enumerate(rs.engines) if victim in e.slots)
    rs.kill_replica(killed_on)
    rs.drain(max_steps=200)
    clone = rs.requeued[0]
    assert clone.done
    assert clone.tokens_out == ref[0].tokens_out, (clone.tokens_out, ref[0].tokens_out)


def test_double_failover_still_token_exact(tiny_model):
    """Two successive replica deaths: the rebuild must never double-bake
    emitted tokens into the prompt (prompt_carried bookkeeping)."""
    from repro.serve.elastic import ReplicaSet
    cfg, model, params = tiny_model
    ref, _ = _run(model, params, cfg, [8], ServeConfig(max_batch=1, max_len=64),
                  max_new=10, seed=5)
    scfg = ServeConfig(max_batch=1, max_len=64)
    rs = ReplicaSet([ServeEngine(model, params, CCFG, scfg) for _ in range(3)])
    victim = _requests(cfg, [8], max_new=10, seed=5)[0]
    rs.submit(victim)
    live = victim
    for _ in range(2):                         # kill whichever replica holds it, twice
        for _ in range(3):
            rs.step()
        holder = next(i for i, e in enumerate(rs.engines)
                      if live in e.slots and rs.health[i].alive)
        rs.kill_replica(holder)
        live = rs.requeued[-1]
    rs.drain(max_steps=300)
    assert live.done
    assert live.tokens_out == ref[0].tokens_out, (live.tokens_out, ref[0].tokens_out)


def test_unservable_prompts_rejected_not_crashed(tiny_model):
    """Oversized and empty prompts are rejected at admission (never crash or
    clobber the cache); the queue behind them still drains."""
    cfg, model, params = tiny_model
    for batched in (True, False):
        reqs, eng = _run(model, params, cfg, [30, 0, 6],
                         ServeConfig(max_batch=2, max_len=16, batched=batched,
                                     prefill_chunk=8), max_new=3)
        assert reqs[0].done and reqs[0].tokens_out == []   # too long
        assert reqs[1].done and reqs[1].tokens_out == []   # empty
        assert reqs[2].done and len(reqs[2].tokens_out) == 3
        assert not eng.busy()


# ---------------------------------------------------------------------------
# CREST through the batched path
# ---------------------------------------------------------------------------

def test_crest_confirms_faults_through_batched_engine(tiny_model):
    from repro.core import crest as crest_mod
    cfg, model, params = tiny_model
    scfg = ServeConfig(max_batch=2, max_len=48, batched=True,
                       crest_enabled=True, crest_every=1,
                       crest_cfg=crest_mod.CrestConfig(n_spares=8, threshold=2))
    eng = ServeEngine(model, params, CCFG, scfg)
    assert eng.batched
    eng.fault_mask = crest_mod.inject_column_faults(jax.random.PRNGKey(7), cfg.vocab, 3)
    for r in _requests(cfg, [8] * 4, max_new=16):
        eng.submit(r)
    eng.run_until_drained(200)
    for _ in range(3 * cfg.vocab // scfg.crest_cfg.n_spares):
        eng._steps += 1
        eng._crest_probe()
    rep = eng.crest_report()
    assert rep["confirmed_faults"] >= 3, rep
    assert rep["repaired"] >= 3, rep


# ---------------------------------------------------------------------------
# cache plumbing
# ---------------------------------------------------------------------------

def test_kv_dtype_plumbs_into_stacked_cache(tiny_model):
    cfg, model, params = tiny_model
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32,
                         kv_dtype=jnp.float8_e4m3fn)
    eng = ServeEngine(model, params, ccfg,
                      ServeConfig(max_batch=2, max_len=64, batched=True))
    leaves = jax.tree.leaves(eng.cache)
    kv = [l for l in leaves if l.ndim >= 4]     # (L, B, T, H, D) buffers
    assert kv and all(l.dtype == jnp.float8_e4m3fn for l in kv)
    # prefill-built caches follow too
    toks = jnp.zeros((1, 8), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks}, ccfg, max_len=16)
    assert cache["layers"]["k"].dtype == jnp.float8_e4m3fn


def test_cache_slot_roundtrip(family_model):
    """write_cache(cache_at(...)) is the failover handoff primitive: a slot
    written into a stacked grid reads back bit-identical — for every cache
    family (probe-discovered slot axes, incl. Python-list sub-caches)."""
    fam, cfg, model, params = family_model
    toks = jnp.asarray(np.arange(8)[None, :], jnp.int32)
    _, sub = model.prefill(params, {"tokens": toks}, CCFG, max_len=16)
    stacked = model.init_cache(4, 16, dtype=jnp.float32)
    stacked = model.write_cache(stacked, sub, 2)
    back = model.cache_at(stacked, 2)
    for a, b in zip(jax.tree.leaves(sub), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    # stack_caches builds the same grid from per-request caches
    restacked = model.stack_caches([model.cache_at(stacked, i) for i in range(4)])
    for a, b in zip(jax.tree.leaves(restacked), jax.tree.leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_prefill_extend_matches_prefill(family_model):
    """Chunked extend over a fresh cache == one-shot prefill (logits of the
    last prompt token match; KV families also write identical positions)."""
    fam, cfg, model, params = family_model
    prompt = np.arange(11, dtype=np.int32) % cfg.vocab
    logits_p, cache_p = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None, :])}, CCFG, max_len=16)
    cache = model.init_cache(1, 16, dtype=jnp.float32)
    logits_e = None
    for start in range(0, len(prompt), 4):
        piece = prompt[start:start + 4]
        toks = np.zeros((1, 4), np.int32)
        toks[0, :len(piece)] = piece
        logits_e, cache = model.prefill_extend(
            params, {"tokens": jnp.asarray(toks)}, cache, CCFG,
            n_valid=jnp.int32(len(piece)))
    # recurrent scans reassociate across chunk boundaries -> fp-level slack
    tol = 1e-5 if fam in ("transformer", "moe") else 1e-4
    np.testing.assert_allclose(np.asarray(logits_e), np.asarray(logits_p),
                               atol=tol, rtol=tol)
    if fam == "transformer":
        np.testing.assert_array_equal(np.asarray(cache["layers"]["pos"]),
                                      np.asarray(cache_p["layers"]["pos"]))


# ---------------------------------------------------------------------------
# per-request latency telemetry (the injected clock)
# ---------------------------------------------------------------------------

def test_per_token_telemetry_and_latency_percentiles(tiny_model):
    """Every committed token carries a clock stamp: token_times parallels
    tokens_out, first_token_at is the first stamp, and metrics() exposes
    TTFT / inter-token percentiles computed from retired requests."""
    from repro.serve.traffic import VirtualClock
    cfg, model, params = tiny_model
    clk = VirtualClock(start=100.0)
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=2, max_len=64, batched=True),
                      clock=clk)
    reqs = _requests(cfg, [8, 8, 8], max_new=4)
    for r in reqs:
        eng.submit(r)
        assert r.created_at == 100.0            # stamped on submit
    while eng.busy():
        clk.advance(0.5)                        # harness-advanced time
        eng.step()
    for r in reqs:
        assert r.done
        assert len(r.token_times) == len(r.tokens_out)
        assert r.first_token_at == r.token_times[0] > r.created_at
        assert all(b >= a for a, b in zip(r.token_times, r.token_times[1:]))
    m = eng.metrics()
    for k in ("ttft_p50_s", "ttft_p99_s",
              "inter_token_p50_s", "inter_token_p99_s"):
        assert m[k] > 0.0, k
    assert m["ttft_p50_s"] <= m["ttft_p99_s"]
    assert m["inter_token_p50_s"] <= m["inter_token_p99_s"]


def test_spec_commit_burst_has_zero_intra_run_gaps(tiny_model):
    """Speculative decode commits a whole accepted run at ONE instant:
    intra-run inter-token gaps are honestly 0 (a client sees the burst),
    and the telemetry must record that rather than fabricate spacing."""
    from repro.serve.traffic import VirtualClock
    cfg, model, params = tiny_model
    clk = VirtualClock()
    eng = ServeEngine(model, params, CCFG,
                      ServeConfig(max_batch=1, max_len=128, batched=True,
                                  draft_len=4),
                      clock=clk)
    rng = np.random.default_rng(0)
    pat = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    eng.submit(Request(uid=0, prompt=np.tile(pat, 4), max_new_tokens=12))
    while eng.busy():
        clk.advance(1.0)
        eng.step()
    req = eng._retired[-1]
    assert eng.spec and len(req.token_times) == len(req.tokens_out)
    gaps = [b - a for a, b in zip(req.token_times, req.token_times[1:])]
    assert any(g == 0.0 for g in gaps), "accepted runs commit at one instant"
    assert all(g in (0.0, 1.0) for g in gaps)


def test_rejected_requests_excluded_from_latency_stats(tiny_model):
    """Rejected (never-served) requests have no first token; they must not
    poison the TTFT percentiles but must count in requests_rejected."""
    cfg, model, params = tiny_model
    reqs, eng = _run(model, params, cfg, [0, 8],
                     ServeConfig(max_batch=1, max_len=64, batched=True),
                     max_new=3)
    m = eng.metrics()
    assert m["requests_rejected"] == 1
    assert m["requests_finished"] == 1
    assert reqs[0].first_token_at == 0.0 and reqs[0].token_times == []
    assert m["ttft_p50_s"] > 0.0                # from the served request only
