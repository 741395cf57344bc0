"""Paged KV pool + radix-tree prefix cache: token-exactness, downgrades,
COW, eviction, failover — plus hypothesis property tests on the host-side
allocator/tree.

The paged engine's contract is TOKEN-EXACT parity with the dense PR-8
engine (not allclose): the gathered page views reconstruct exactly what
the dense cache would attend over, garbage rows mask to softmax weight
0.0, and the radix tree only ever re-pins pages whose contents encode the
matched prefix. Every parity test here serves a shared-system-prompt
request mix through both engines and compares streams token-for-token.
"""
import jax

# sampled parity compares engines constructed in one process: the flag must
# flip BEFORE any params are drawn (see the engine's construction warning)
jax.config.update("jax_threefry_partitionable", True)
jax.config.update("jax_platform_name", "cpu")

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cascade import CascadeConfig
from repro.models import registry
from repro.serve.elastic import ReplicaSet
from repro.serve.engine import Request, ServeConfig, ServeEngine
from repro.serve.prefix import PagePool, RadixPrefixCache
from repro.serve.traffic import VirtualClock

CCFG = CascadeConfig(mode="train", compute_dtype=jnp.float32)

#: full-attention families take the page-gather read path; ring-buffer /
#: recurrent state has no page-granular layout and must downgrade
PAGED_FAMILIES = ["transformer", "moe"]
DENSE_FAMILIES = ["griffin", "ssm"]


def _load(family):
    cfg, model = registry.load(registry.FAMILY_SMOKE[family], smoke=True)
    params = model.init_params(jax.random.PRNGKey(0), CCFG)
    return cfg, model, params


@pytest.fixture(scope="module")
def transformer():
    return _load("transformer")


def _shared_reqs(cfg, n=4, prefix_len=24, suffix_len=6, max_new=8, seed=0):
    """n requests sharing one prefix (fresh objects every call — requests
    are mutated by the engine, so parity runs each need their own)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, prefix_len).astype(np.int32)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [shared,
                         rng.integers(0, cfg.vocab, suffix_len).astype(np.int32)]),
                    max_new_tokens=max_new)
            for i in range(n)]


def _run(model, reqs, params, *, prefix_cache, draft_len=0, temperature=0.0,
         batched=True, max_batch=2, num_pages=0, page_size=8):
    scfg = ServeConfig(max_batch=max_batch, max_len=64, batched=batched,
                       prefill_chunk=8, draft_len=draft_len,
                       temperature=temperature, top_k=8,
                       prefix_cache=prefix_cache, page_size=page_size,
                       num_pages=num_pages)
    eng = ServeEngine(model, params, CCFG, scfg)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, [list(r.tokens_out) for r in reqs]


# ---------------------------------------------------------------------------
# token-exact parity vs the dense engine, per supported family x mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", PAGED_FAMILIES)
def test_paged_greedy_token_exact(family):
    """Paged + prefix-cached serving of a shared-prefix mix emits exactly
    the dense engine's greedy tokens, with real prefix hits along the way."""
    cfg, model, params = _load(family)
    _, ref = _run(model, _shared_reqs(cfg), params, prefix_cache=False)
    eng, out = _run(model, _shared_reqs(cfg), params, prefix_cache=True)
    assert eng.paged and eng.effective_mode == "batched-greedy-paged"
    assert not eng.downgrades
    assert out == ref
    assert eng.metrics()["prefix_hits"] > 0


def test_paged_spec_token_exact(transformer):
    """Speculative decode on the paged pool (checkpoint carries the block
    table; rewind restores through it) commits exactly the dense spec
    engine's tokens."""
    cfg, model, params = transformer
    _, ref = _run(model, _shared_reqs(cfg), params, prefix_cache=False,
                  draft_len=3)
    eng, out = _run(model, _shared_reqs(cfg), params, prefix_cache=True,
                    draft_len=3)
    assert eng.effective_mode == "spec-greedy-paged"
    assert out == ref


def test_paged_sampled_token_exact(transformer):
    """Seeded sampling: identical logits through the page gather + the same
    fold_in draw order means identical realizations token-for-token."""
    cfg, model, params = transformer
    _, ref = _run(model, _shared_reqs(cfg), params, prefix_cache=False,
                  temperature=0.7)
    eng, out = _run(model, _shared_reqs(cfg), params, prefix_cache=True,
                    temperature=0.7)
    assert eng.effective_mode == "batched-sampled-paged"
    assert out == ref


def test_paged_spec_sampled_token_exact(transformer):
    """Speculative SAMPLING (rejection resampling) over paged state stays
    realization-exact with the dense engine."""
    cfg, model, params = transformer
    _, ref = _run(model, _shared_reqs(cfg), params, prefix_cache=False,
                  draft_len=3, temperature=0.7)
    eng, out = _run(model, _shared_reqs(cfg), params, prefix_cache=True,
                    draft_len=3, temperature=0.7)
    assert eng.effective_mode == "spec-sampled-paged"
    assert out == ref


def test_paged_without_prefix_cache_token_exact(transformer):
    """paged=True alone (no radix tree) is the pure pool refactor: same
    tokens, zero prefix machinery engaged."""
    cfg, model, params = transformer
    _, ref = _run(model, _shared_reqs(cfg), params, prefix_cache=False)
    scfg = ServeConfig(max_batch=2, max_len=64, batched=True, prefill_chunk=8,
                       paged=True, page_size=8)
    eng = ServeEngine(model, params, CCFG, scfg)
    reqs = _shared_reqs(cfg)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert eng.paged and eng.prefix is None
    assert [list(r.tokens_out) for r in reqs] == ref
    assert eng.metrics()["prefix_lookups"] == 0


# ---------------------------------------------------------------------------
# failover: kill a paged replica mid-decode, survivors re-pin token-exactly
# ---------------------------------------------------------------------------

def _fleet_run(model, params, reqs, *, prefix_cache):
    clk = VirtualClock()
    scfg = ServeConfig(max_batch=2, max_len=64, batched=True, prefill_chunk=8,
                       prefix_cache=prefix_cache, page_size=8)
    rs = ReplicaSet([ServeEngine(model, params, CCFG, scfg, clock=clk)
                     for _ in range(2)],
                    clock=clk, step_cost=lambda i: 0.01)
    for r in reqs:
        rs.submit(r)
    for _ in range(6):
        rs.step()
    rs.kill_replica(0)
    rs.drain(max_steps=2000)
    final = {}
    for e in rs.engines:
        for r in e._retired:
            cur = final.get(r.uid)
            if cur is None or len(r.tokens_out) > len(cur.tokens_out):
                final[r.uid] = r
    return rs, {u: list(r.tokens_out) for u, r in final.items()}


def test_paged_failover_token_exact(transformer):
    """A hard replica loss mid-decode: aborted slots release their pages,
    rebuilt streams re-admit (and re-pin shared pages) on the survivor, and
    every stream's final tokens match the dense fleet run exactly."""
    cfg, model, params = transformer
    _, ref = _fleet_run(model, params,
                        _shared_reqs(cfg, n=4, max_new=16),
                        prefix_cache=False)
    rs, out = _fleet_run(model, params,
                         _shared_reqs(cfg, n=4, max_new=16),
                         prefix_cache=True)
    assert out == ref
    # the killed engine's slots all released their pages: whatever is still
    # resident is tree-held only (refcount exactly 1, trash page aside)
    dead = rs.engines[0]
    assert dead.paged
    held = dead.pool.refcount[1:]
    assert (held[held > 0] == 1).all(), held


def test_paged_abort_releases_every_page(transformer):
    """abort_in_flight on a prefix-cached engine leaves no slot-held pages
    behind — only tree refs survive, and evicting the whole tree drains the
    pool to empty (no leaked refcounts)."""
    cfg, model, params = transformer
    scfg = ServeConfig(max_batch=2, max_len=64, batched=True, prefill_chunk=8,
                       prefix_cache=True, page_size=8)
    eng = ServeEngine(model, params, CCFG, scfg)
    for r in _shared_reqs(cfg, n=3, max_new=32):
        eng.submit(r)
    for _ in range(6):
        eng.step()
    assert eng.busy()
    eng.abort_in_flight()
    held = eng.pool.refcount[1:]
    assert (held[held > 0] == 1).all(), held          # tree-only residents
    eng.prefix.evict(eng.pool.num_pages)              # drop the whole tree
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# COW + eviction behavior
# ---------------------------------------------------------------------------

def test_paged_cow_divergent_suffix_token_exact(transformer):
    """Two prompts diverging MID-page: the second admission clones the
    partially shared page (COW) instead of re-prefilling it — and instead
    of corrupting the first stream's published page. Serial admission
    (max_batch=1) forces the second request to see the first's tree entry."""
    cfg, model, params = transformer

    def reqs():
        rng = np.random.default_rng(3)
        shared = rng.integers(0, cfg.vocab, 12).astype(np.int32)  # 1.5 pages
        tails = [rng.integers(0, cfg.vocab, 6).astype(np.int32)
                 for _ in range(2)]
        return [Request(uid=i, prompt=np.concatenate([shared, tails[i]]),
                        max_new_tokens=8) for i in range(2)]

    _, ref = _run(model, reqs(), params, prefix_cache=False, max_batch=1)
    eng, out = _run(model, reqs(), params, prefix_cache=True, max_batch=1)
    assert out == ref
    # page_size 8, shared 12 tokens: one full-page hit (8) + a 4-token COW
    # tail => more hit tokens than the full pages alone account for
    assert eng.metrics()["prefix_hits"] > 8


def test_paged_eviction_under_pool_pressure(transformer):
    """Distinct prompts through a deliberately tight pool: the watermark
    evicts LRU tree-only pages to keep admission allocable, and the streams
    stay token-exact with the dense engine throughout."""
    cfg, model, params = transformer

    def reqs():
        rng = np.random.default_rng(7)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, 30).astype(np.int32),
                        max_new_tokens=6) for i in range(4)]

    _, ref = _run(model, reqs(), params, prefix_cache=False, max_batch=1)
    # max_len 64 / page_size 8 -> 8 blocks per slot; 9 pages is the minimum
    # pool (1 slot + trash), so every retained tree page fights the watermark
    eng, out = _run(model, reqs(), params, prefix_cache=True, max_batch=1,
                    num_pages=9)
    assert out == ref
    m = eng.metrics()
    assert m["evictions"] > 0
    assert m["pages_in_use"] <= m["pages_total"]


def test_paged_hit_rate_meets_bar(transformer):
    """The acceptance-criteria bar, unit-pinned: a shared-system-prompt mix
    (long shared prefix, short unique tail) serves with prefix_hit_rate
    >= 0.5 — most prompt tokens come from resident pages, not prefill."""
    cfg, model, params = transformer
    eng, _ = _run(model,
                  _shared_reqs(cfg, n=6, prefix_len=24, suffix_len=6),
                  params, prefix_cache=True, max_batch=1)
    m = eng.metrics()
    assert m["prefix_lookups"] == 6 * 30
    assert m["prefix_hit_rate"] >= 0.5, m["prefix_hit_rate"]
    assert m["pages_in_use"] > 0


# ---------------------------------------------------------------------------
# downgrades: never silently run a different path than reported
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", DENSE_FAMILIES)
def test_paged_downgrades_on_non_attention_state(family):
    """Ring-buffer / recurrent caches have no page-granular layout: the
    engine must record the downgrade, warn once, report an un-suffixed
    mode — and still serve the exact dense tokens."""
    cfg, model, params = _load(family)
    _, ref = _run(model, _shared_reqs(cfg, n=2, max_new=4), params,
                  prefix_cache=False)
    with pytest.warns(RuntimeWarning, match="paged KV requested"):
        eng, out = _run(model, _shared_reqs(cfg, n=2, max_new=4), params,
                        prefix_cache=True)
    assert not eng.paged and eng.prefix is None
    assert not eng.effective_mode.endswith("-paged")
    assert any("paged" in d for d in eng.downgrades)
    assert out == ref


def test_paged_downgrades_on_slotwise_path(transformer):
    cfg, model, params = transformer
    with pytest.warns(RuntimeWarning, match="paged KV requested"):
        eng, _ = _run(model, _shared_reqs(cfg, n=1, max_new=2), params,
                      prefix_cache=True, batched=False)
    assert not eng.paged
    assert not eng.effective_mode.endswith("-paged")


def test_paged_metrics_keys(transformer):
    cfg, model, params = transformer
    eng, _ = _run(model, _shared_reqs(cfg, n=2, max_new=2), params,
                  prefix_cache=True)
    m = eng.metrics()
    assert m["paged"] is True and m["prefix_cache"] is True
    for k in ("prefix_hit_rate", "pages_in_use", "pages_total", "evictions",
              "page_size", "prefix_hits", "prefix_lookups"):
        assert k in m, k
    assert m["page_size"] == 8
    assert m["effective_mode"].endswith("-paged")


# ---------------------------------------------------------------------------
# hypothesis property tests: the host-side allocator + radix tree
# ---------------------------------------------------------------------------

_seeds = st.integers(0, 2**31 - 1)


@settings(max_examples=50, deadline=None)
@given(_seeds, st.integers(2, 24))
def test_pool_alloc_release_invariants(seed, num_pages):
    """Random alloc/ref/release interleavings preserve the pool's books:
    no double-assignment, page 0 pinned, counts never negative, and
    free + in_use always partitions the allocatable pages."""
    rng = np.random.default_rng(seed)
    pool = PagePool(num_pages)
    live = {}                                    # page -> our refcount
    for _ in range(200):
        op = rng.integers(0, 3)
        if op == 0 and pool.free_pages:
            pg = pool.alloc()
            assert pg not in live and 0 < pg < num_pages
            live[pg] = 1
        elif op == 1 and live:
            pg = int(rng.choice(list(live)))
            pool.ref(pg)
            live[pg] += 1
        elif op == 2 and live:
            pg = int(rng.choice(list(live)))
            pool.release(pg)
            live[pg] -= 1
            if live[pg] == 0:
                del live[pg]
        assert pool.refcount[0] == 1             # trash page pinned
        assert (pool.refcount >= 0).all()
        assert pool.free_pages + pool.pages_in_use == num_pages - 1
        assert pool.pages_in_use == len(live)
        for pg, n in live.items():
            assert pool.refcount[pg] == n


@settings(max_examples=30, deadline=None)
@given(_seeds, st.integers(2, 4), st.integers(1, 5))
def test_radix_insert_match_evict_roundtrip(seed, page_size, n_prompts):
    """insert -> match returns the SAME physical pages for every full page
    of the prompt (capped at len-1); releasing all slot/match refs and
    evicting everything drains the pool to zero — no leaked refcounts."""
    rng = np.random.default_rng(seed)
    pool = PagePool(64)
    tree = RadixPrefixCache(pool, page_size)
    published = {}
    for _ in range(n_prompts):
        prompt = [int(t) for t in rng.integers(0, 3, rng.integers(1, 13))]
        n_full = len(prompt) // page_size
        slot_pages = [pool.alloc() for _ in range(n_full)]
        tree.insert(prompt, slot_pages)
        for pg in slot_pages:                    # slot retires
            pool.release(pg)
        published[tuple(prompt)] = True
        m = tree.match(prompt)
        # a full page only matches if it fits under the len-1 cap
        want_full = min(n_full * page_size, len(prompt) - 1) // page_size
        assert len(m.pages) == want_full
        assert m.matched == want_full * page_size
        for pg in m.pages:
            assert pool.refcount[pg] == 2        # tree + our match ref
            pool.release(pg)
    tree.evict(pool.num_pages)
    assert pool.pages_in_use == 0
    for prompt in published:
        m = tree.match(list(prompt))
        assert m.pages == [] and m.matched == 0


@settings(max_examples=30, deadline=None)
@given(_seeds, st.integers(2, 4))
def test_radix_cow_never_mutates_shared_page(seed, page_size):
    """A divergent-tail match clones the shared page through the COW hook:
    the original page's (host-simulated) contents are untouched, the clone
    is a distinct page, and the tree still holds the original."""
    rng = np.random.default_rng(seed)
    pool = PagePool(32)
    store = {}                                   # page -> contents snapshot

    def copy_page(src):
        dst = pool.alloc() if pool.free_pages else None
        if dst is not None:
            store[dst] = list(store[src])
        return dst

    tree = RadixPrefixCache(pool, page_size, copy_page=copy_page)
    prompt = [int(t) for t in rng.integers(0, 3, 2 * page_size)]
    pages = [pool.alloc() for _ in range(2)]
    for pg, i in zip(pages, range(2)):
        store[pg] = prompt[i * page_size:(i + 1) * page_size]
    tree.insert(prompt, pages)
    for pg in pages:
        pool.release(pg)
    # diverge INSIDE the second page (first token of it), keep one extra
    # token so the len-1 cap still allows the full first page
    div = list(prompt)
    div[page_size] = (div[page_size] + 1) % 3
    div.append(0)
    before = {pg: list(c) for pg, c in store.items()}
    m = tree.match(div)
    assert m.pages[0] == pages[0]                # full-page hit, same page
    if len(m.pages) > 1:                         # COW tail engaged
        assert m.cow
        clone = m.pages[-1]
        assert clone not in pages
        assert store[clone] == before[pages[1]]  # copied at clone time
    for pg in pages:                             # originals unmodified
        assert store[pg] == before[pg]
    assert tree.match(prompt + [0]).pages[:2] == pages  # tree intact: both
                                                        # originals still hit


@settings(max_examples=30, deadline=None)
@given(_seeds)
def test_radix_eviction_is_lru_and_bounded(seed):
    """evict(n) frees at most n pages, only tree-only (refcount-1) pages,
    in least-recently-used order; slot-pinned pages are never victims."""
    rng = np.random.default_rng(seed)
    pool = PagePool(64)
    tree = RadixPrefixCache(pool, page_size=2)
    prompts = [[i, i, i, i] for i in range(5)]
    pages = {}
    for p in prompts:
        pgs = [pool.alloc(), pool.alloc()]
        tree.insert(p, pgs)
        pages[tuple(p)] = pgs
        for pg in pgs:
            pool.release(pg)
    # touch prompts in a random order; then pin one prompt's pages as if a
    # slot still mapped them
    order = list(rng.permutation(len(prompts)))
    for i in order:
        m = tree.match(prompts[i] + [9])
        for pg in m.pages:
            pool.release(pg)
    pinned = prompts[order[0]]                   # least recently used
    mp = tree.match(pinned + [9])                # pin via match refs
    assert mp.pages
    before = pool.pages_in_use
    freed = tree.evict(3)
    assert freed <= 3
    assert pool.pages_in_use == before - freed
    # the pinned (refcount-2) pages survived even though they are LRU
    for pg in pages[tuple(pinned)]:
        assert pool.refcount[pg] >= 1
    m2 = tree.match(pinned + [9])
    assert m2.pages == mp.pages
    for pg in mp.pages + m2.pages:
        pool.release(pg)
