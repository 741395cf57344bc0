"""Serving engine: batched continuous batching over a fixed slot grid, FP4
weights, chunked prefill, CREST runtime fault detection, straggler guard.

The ZettaLith analogy (paper Sections 14, 19-20): a rack serves one model
from resident (HBM) FP4 weights; batch size is chosen to balance HBM weight
streaming against compute (Table 9/10); CREST continuously shadow-tests
columns; failed components are mapped out without draining traffic.

Software mapping: ``ServeEngine`` owns a slot grid of ``max_batch`` decode
streams backed by ONE stacked, fixed-shape KV cache pytree. Each step:

1. **admission** — queued requests are prefilled into free slots in
   ``prefill_chunk``-token pieces (fixed chunk shape => one compiled extend
   kernel for any prompt length), bounded by a per-step ``token_budget`` so
   decode latency for already-resident streams stays bounded;
2. **decode** — ONE donated, jitted batched ``decode_step`` runs over the
   whole slot grid (weight streaming is paid once per step, not once per
   request — the CASCADE batching analysis, Table 9/10); inactive slots
   compute masked garbage that never escapes. With ``draft_len > 0`` the
   step instead runs **speculative decode**: a model-free prompt-lookup
   drafter (``serve/spec.py``) proposes up to K tokens per slot (reporting
   the per-slot effective draft length ``k_eff`` — padding is never scored
   as a proposal), ONE batched verify pass (the fixed-shape
   ``prefill_extend`` path) scores all K+1 positions at once, accepted
   tokens commit (plus a bonus/resampled token), and the rejected suffix
   rolls back through per-family cache rewind ops (``spec_rewind``) —
   weight streaming is amortized over every accepted token. Acceptance
   depends on the decoding mode: under greedy the longest draft prefix
   matching the model's own argmax commits, and the emitted stream is
   token-exact with plain greedy decode; under sampling
   (``temperature > 0``) the step runs **speculative sampling** (rejection
   resampling, ``spec_sample_accept``) against the drafter's point-mass
   proposal distribution, so every committed token is distributed EXACTLY
   as plain sampled decode (distribution-exact, not token-exact — the
   draws differ but the law does not);
3. a CREST probe wave optionally shadow-tests the lm_head matmul;
4. finished streams retire by simply freeing their slot — admission and
   retirement are cache-slot writes, so nothing ever recompiles as traffic
   comes and goes.

**Mesh-native serving** (paper Sections 2.2, 13.5): pass ``mesh=`` (a
``(data, model)`` jax mesh) and the whole batched serving stack — decode,
chunked prefill, spec-verify, rewind — runs under ``NamedSharding``: params
are placed by the ``tp_policy`` (``cascade`` column-parallel, the paper's
layout, or the ``megatron`` row+column baseline) via
``distributed.sharding.param_specs``, and the stacked cache shards its
probe-discovered slot axis over ``data`` (``cache_pspecs`` — every data
shard owns a band of decode slots). Under the cascade policy the decode
step contains **zero partial-sum all-reduce** — activations are broadcast,
weights are column-sharded, reductions stay local — and
``decode_step_hlo()`` exposes the compiled HLO so
``benchmarks/hlo_analysis.partial_sum_allreduces`` can assert the paper's
headline interconnect claim as an executable test. The sharded path is
token-exact with the single-device path (contractions never split, so
accumulation order is unchanged).

Every registry arch family runs the batched fast path over its own cache
state:

==============  ===========================  ==============================
family          stacked state per slot       chunked prefill
==============  ===========================  ==============================
transformer     full-attention KV            fixed-shape padded chunks
moe (MLA/GQA)   latent c_kv + rope key / KV  fixed-shape padded chunks
griffin/hybrid  ring-buffer KV + {conv, h}   ring-aware (never clobbers
                                             in-window entries)
ssm (mamba2)    {conv, ssd state}            dt=0 passthrough padding
==============  ===========================  ==============================

Windowed/recurrent archs hold O(window)/O(1) state, so their admissible
prompt length is NOT bounded by ``max_len`` (window-aware admission) and
they never retire on a context limit. ``batched=False`` keeps the legacy
slot-wise loop as the parity baseline; multi-codebook heads (musicgen)
remain slot-wise. Decoding is greedy argmax by default; ``temperature`` /
``top_k`` switch on (deterministic, seeded) sampling — drawn ON DEVICE
everywhere (``jax.random.categorical`` fused into the jitted step for the
batched grid; a jitted single-row draw for the admission and slot-wise
paths) under ONE shared RNG discipline: draw i uses
``fold_in(PRNGKey(sample_seed), i)`` regardless of mode — a speculative
sampled step consumes exactly one counter value and derives its accept
uniforms and resample/bonus Gumbel noise from it on device. Any mode
downgrade (multi-codebook models dropping to the slot-wise grid, a model
missing the spec API, slot-wise engines dropping speculation) warns once
and is visible as ``metrics()['effective_mode']``. ``elastic.py`` handles
replica failure
by re-queueing in-flight requests (decode state — including recurrent
state — is reconstructible from the prompt + emitted tokens; ``tokens_out``
only ever holds verify-committed tokens, so a failover can never carry an
unaccepted draft).
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import crest
from repro.core.cascade import CascadeConfig
from repro.distributed import sharding as shd
from repro.models.cache_utils import reset_slot_pos
from repro.serve import telemetry
from repro.serve.prefix import PagePool, RadixPrefixCache
from repro.serve.spec import ngram_propose
from repro.serve.traffic import MonotonicClock

#: methods a model must expose for the batched (stacked-cache) fast path
#: (``stack_caches``/``cache_at`` are companion utilities on the model, but
#: the engine itself only needs slot writes + chunked extend)
_BATCHED_API = ("write_cache", "prefill_extend")

#: additional methods required for speculative decode (verify pass + per-
#: family cache rewind)
_SPEC_API = ("spec_verify", "spec_rewind")


def _truncate_logits(logits, temperature: float, top_k: int):
    """Temperature-scale + top-k-truncate logits; ``softmax`` of the result
    is THE sampling distribution p every sampled path draws from.

    Works on any ``(..., V)`` shape (decode rows are ``(B, V)``; the
    speculative verify pass truncates all ``(B, K+1, V)`` rows at once —
    acceptance must score drafts against the IDENTICAL truncated p that
    plain decode samples from, or the committed distribution drifts).

    **Tie semantics (documented, pinned by tests):** the truncated support
    is VALUE-defined, not count-defined — every logit ``>= kth`` survives,
    so a tie at the k-th logit keeps all tied candidates (more than k).
    This makes the truncation a pure function of the logit values (no
    arbitrary index-order tie-break that plain decode and the verify pass
    could resolve differently), which is what distribution-exact
    speculative sampling requires. Corollary: ``top_k=1`` equals greedy
    only when the max is unique.

    Under a cascade mesh policy the rows are pinned replicated first (one
    small all-gather): top-k / softmax / the Gumbel add over a
    vocab-sharded row would otherwise lower to a partial-sum all-reduce,
    breaking the zero-AR invariant for sampled serving.
    """
    x = shd.constrain_replicated(logits).astype(jnp.float32) / temperature
    if 0 < top_k < x.shape[-1]:
        kth = jax.lax.top_k(x, top_k)[0][..., -1:]
        x = jnp.where(x < kth, -jnp.inf, x)
    return x


def _sample_tokens(logits, key, temperature: float, top_k: int):
    """(B, V) logits -> (B,) sampled token ids, entirely on device.

    Each row's draw is a pure function of (key, row index): the Gumbel
    noise is positional, so an active slot's sample never depends on what
    garbage the inactive slots hold.
    """
    return jax.random.categorical(
        key, _truncate_logits(logits, temperature, top_k), axis=-1)


#: large finite logit penalty used to zero the rejected draft's mass in the
#: residual. Finite on purpose: if the residual is EMPTY (p was numerically
#: a point mass on the draft, so every other logit is already -inf), the
#: penalized draft still wins the categorical — which is the correct
#: degenerate action, because an empty residual means the acceptance
#: probability was 1 and the "rejection" was a measure-zero float artifact.
_RESIDUAL_PENALTY = 1e30


def spec_sample_accept(logits, drafts, k_eff, key, temperature: float,
                       top_k: int):
    """Speculative-sampling acceptance for a point-mass (delta) drafter.

    Args: ``logits`` (B, K+1, V) verify-pass rows (row j conditions on the
    cache prefix + chunk tokens 0..j); ``drafts`` (B, K) proposed tokens
    (chunk tokens 1..K); ``k_eff`` (B,) real-proposal counts (positions
    >= k_eff are padding and are force-rejected, never scored); ``key`` ONE
    fold_in counter value — accept uniforms and the resample/bonus draw are
    derived from it on device, positionally per slot.

    Returns ``(a, token)``: ``a`` (B,) accepted draft counts and ``token``
    (B,) the step's final committed token. The standard rule, specialized
    to q = delta(d):

    * accept draft d_j with probability ``min(1, p_j(d_j) / q_j(d_j))`` =
      ``p_j(d_j)`` (q is a point mass, so the clamp never binds; p is the
      truncated softmax ``_truncate_logits`` defines — identical to what
      plain sampled decode draws from);
    * first rejection at row a: resample from the residual
      ``norm(max(0, p_a - q_a))`` — for a delta q that is p_a with the
      rejected token's mass removed, i.e. a logit-space mask of d_a;
    * all k_eff real drafts accepted: the bonus token is drawn from row
      ``k_eff`` (NOT row K when k_eff < K — later rows condition on padded
      tokens that were never proposed).

    Marginal of the committed token at any row: ``p(d)·1[t=d] +
    (1-p(d))·p(t)/(1-p(d)) = p(t)`` — exactly the plain sampled-decode
    distribution, which is the tentpole's distribution-exactness argument
    (enumerated per family by ``tests/test_spec.py``).
    """
    b, kp1, v = logits.shape
    k = kp1 - 1
    x = _truncate_logits(logits, temperature, top_k)        # (B, K+1, V)
    logp = jax.nn.log_softmax(x, axis=-1)
    # p_j(d_j): the truncated model probability of each draft token
    p_draft = jnp.exp(jnp.take_along_axis(
        logp[:, :k], drafts[..., None], axis=-1)[..., 0])   # (B, K)
    key_u, key_t = jax.random.split(key)
    u = jax.random.uniform(key_u, (b, k))
    real = jnp.arange(k, dtype=jnp.int32)[None, :] < k_eff[:, None]
    accept = (u < p_draft) & real
    # leading-accept count: stop at the first rejection (or at k_eff)
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1), axis=-1)
    # final row: the rejection row (a < k_eff) or the bonus row (a == k_eff)
    row = jnp.take_along_axis(x, a[:, None, None], axis=1)[:, 0]  # (B, V)
    rejected = a < k_eff
    d_rej = jnp.take_along_axis(drafts, jnp.minimum(a, k - 1)[:, None],
                                axis=1)[:, 0]               # (B,)
    mask = (jnp.arange(v, dtype=jnp.int32)[None, :] == d_rej[:, None])
    row = row - jnp.where(mask & rejected[:, None], _RESIDUAL_PENALTY, 0.0)
    token = jax.random.categorical(key_t, row, axis=-1)
    return a, token


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    created_at: float = 0.0       # arrival: pre-stamped by an open-loop
                                  # driver, else set at first submit()
    admitted_at: float = 0.0      # when prefill started (admission wait ends)
    first_token_at: float = 0.0
    finished_at: float = 0.0
    tokens_out: list = dataclasses.field(default_factory=list)
    done: bool = False
    prompt_carried: int = 0       # leading tokens_out entries already baked
                                  # into ``prompt`` by a failover rebuild
    # --- per-request latency telemetry (the engine's injected clock) ---
    token_times: list = dataclasses.field(default_factory=list)
    #: token_times[i] is the clock reading when tokens_out[i] was COMMITTED
    #: (post-verification under speculation — a spec step commits its whole
    #: accepted run at one instant, which is the honest burst semantics).
    #: TTFT = first_token_at - created_at; inter-token latencies are the
    #: successive differences of token_times. Failover carries both lists,
    #: so a re-routed stream's record spans replicas seamlessly.
    # --- per-request SLOs (stamped by the traffic generator; 0 = none) ---
    slo_ttft_s: float = 0.0       # TTFT target this request is judged by
    deadline_s: float = 0.0       # admission deadline: the router sheds the
                                  # request if not dispatched within this
                                  # many seconds of arrival
    prefix_id: int = -1           # shared-prefix pool tag from the traffic
                                  # generator (-1 = unique prompt): benches
                                  # split warm vs cold TTFT on it


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256
    eos_id: int = -1              # -1: only stop at max_new_tokens
    crest_enabled: bool = False
    crest_every: int = 4          # run a BIST probe wave every N engine steps
    crest_cfg: crest.CrestConfig = dataclasses.field(default_factory=crest.CrestConfig)
    batched: bool = True          # one jitted decode over the whole slot grid
    prefill_chunk: int = 32       # chunked-prefill piece size (0 = whole prompt;
                                  # clamped to the ring length for windowed archs)
    token_budget: int = 0         # max prompt tokens admitted per step (0 = no cap;
                                  # enforced at chunk granularity)
    temperature: float = 0.0      # <= 0: greedy argmax (the deterministic
                                  # test path); > 0: seeded sampling
    top_k: int = 0                # restrict sampling to the k best logits (0 = all)
    sample_seed: int = 0          # sampling is deterministic given seed + call order
    draft_len: int = 0            # speculative decode: K drafted tokens per slot
                                  # per step (0 = plain one-token decode; clamped
                                  # to window-1 for ring-buffer archs). Greedy
                                  # uses argmax-prefix acceptance; temperature
                                  # > 0 uses speculative SAMPLING (rejection
                                  # resampling — distribution-exact with plain
                                  # sampled decode)
    ngram_max: int = 3            # longest suffix n-gram the prompt-lookup
                                  # drafter tries to match (see serve/spec.py)
    ngram_lookback: int = 512     # drafter scans at most this many trailing
                                  # context tokens (bounds per-step host work)
    tp_policy: str = "cascade"    # param placement when a mesh is passed:
                                  # 'cascade' (column-parallel, zero partial-
                                  # sum all-reduce) or 'megatron' (row+column
                                  # baseline with the classic all-reduce)
    fused: bool = False           # route decode/extend/verify through the
                                  # Pallas kernels (packed-FP4 matmul +
                                  # decode attention). Needs serve_fp4
                                  # params and the batched path; interpret
                                  # mode keeps it runnable (and token-exact
                                  # vs the jnp path) on CPU
    paged: bool = False           # page-granular KV pool: cache leaves are
                                  # (num_pages, page_size, ...) with host-
                                  # owned per-slot block tables; token-exact
                                  # with the dense cache. Full-attention
                                  # archs only (ring/recurrent downgrade
                                  # with a warning); incompatible with mesh
    page_size: int = 16           # tokens per physical page
    num_pages: int = 0            # pool size (0 = auto: max_batch *
                                  # blocks_per_slot + 1 — enough that every
                                  # slot can always fill, plus the trash page)
    prefix_cache: bool = False    # radix-tree prefix cache over token-id
                                  # prompts: admission maps shared prefixes
                                  # to resident pages instead of
                                  # re-prefilling them (implies paged)
    evict_watermark: float = 0.9  # pool-pressure fraction above which LRU
                                  # tree-only pages are evicted at alloc time


@dataclasses.dataclass
class _Staging:
    """A request mid-prefill: holds its batch-1 cache until fully prefilled."""
    req: Request
    cache: Any
    consumed: int
    slot: int


class ServeEngine:
    def __init__(self, model, params, ccfg: CascadeConfig, scfg: ServeConfig,
                 mesh=None, clock=None):
        self.model = model
        self.params = params
        self.ccfg = ccfg
        self.scfg = scfg
        self.mesh = mesh
        # every serving-path latency number (request timestamps, admission
        # waits, step times) reads THIS clock — wall time by default, a
        # shared VirtualClock in deterministic traffic tests (the harness
        # advances it; the engine only reads it)
        self.clock = clock if clock is not None else MonotonicClock()
        # spans and counters of the engine's phases, on the same clock
        self.telemetry = telemetry.Telemetry(self.clock)
        self.tp_policy = scfg.tp_policy
        # the cascade policy installs the activation-broadcast discipline
        # (constrain_* hooks in model code); megatron is the measured GSPMD
        # baseline — no constraints, the partitioner emits its all-reduces
        self._act_policy = "cascade" if scfg.tp_policy == "cascade" else "none"
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * scfg.max_batch
        self.crest_state = None
        self.fault_mask = None          # set by tests/demos to inject faults
        self._probe_w = None
        self._steps = 0
        self.step_times: list = []
        self._decode_tokens = 0
        self._admission_waits: list = []
        self._retired: List[Request] = []
        self._rejected = 0
        self._staging: Optional[_Staging] = None
        # Sampled serving draws random bits INSIDE sharded jitted steps (the
        # fused sampled decode step and the speculative verify+accept step).
        # With the legacy non-partitionable threefry lowering, GSPMD
        # generates DIFFERENT bits when it partitions a generation over the
        # mesh, so a sharded engine's draws would silently diverge from the
        # unsharded engine's — distribution-preserving but realization-
        # breaking (irreproducible across mesh shapes). The partitionable
        # implementation is sharding-invariant by contract and the default
        # in newer jax; opt in for the older pinned versions. Scoped to
        # sampled-engine construction (greedy engines never draw), BEFORE
        # the key below is made, so unrelated code that merely imports this
        # module keeps its RNG streams. It is still a PROCESS-GLOBAL jax
        # flag — every sampled engine must share it (that is what the
        # sharded-vs-unsharded parity contract requires), and any unrelated
        # jax.random use in the same process re-bases its realizations too,
        # so the flip is announced once instead of happening silently.
        if (scfg.temperature > 0.0
                and not jax.config.jax_threefry_partitionable):
            warnings.warn(
                "sampled serving enables jax_threefry_partitionable "
                "(process-global): jax.random realizations drawn after this "
                "point differ from the legacy lowering's; distributions and "
                "seed-determinism are unaffected", RuntimeWarning,
                stacklevel=3)
            # the designated site: sampled engines REQUIRE one shared
            # process-global threefry lowering (sharded-vs-unsharded
            # parity), and the flip warns just above
            # lint: allow[jax-config-global] — designated global-config site
            jax.config.update("jax_threefry_partitionable", True)
        # ONE on-device RNG discipline for every sampling site (batched grid,
        # admission, slot-wise loop): draw i uses fold_in(PRNGKey(seed), i),
        # so all modes are deterministic given seed + draw order and no
        # logits row is ever copied to host just to sample it
        self._sample_key = jax.random.PRNGKey(scfg.sample_seed)
        self._sample_step = 0
        if scfg.temperature > 0.0:
            def _pick_token(row, key):
                return _sample_tokens(row[None, :], key, scfg.temperature,
                                      scfg.top_k)[0]

            self._pick_fn = jax.jit(_pick_token)
        self._accepted_drafts = 0     # drafted tokens the verify pass accepted
        self._spec_slot_steps = 0     # (slot, step) pairs that ran speculation
        # per-slot draft context, appended incrementally as tokens commit
        # (rebuilding prompt+emitted every step would be O(stream^2) host work)
        self._spec_ctx: List[Optional[list]] = [None] * scfg.max_batch

        # Silent mode downgrades are a bug class of their own (a bench that
        # thinks it measured speculation but ran plain decode): every
        # downgrade warns ONCE (at construction) and is recorded so
        # metrics()['effective_mode'] exposes the path that actually runs.
        self.downgrades: List[str] = []

        def _downgrade(msg: str):
            self.downgrades.append(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=3)

        # batched mode needs the stacked-cache API and flat logits
        # (multi-codebook heads only work slot-wise for now); every other
        # registry family — full/windowed attention, MLA, recurrent — runs
        # the batched fast path over its own stacked state
        window = getattr(getattr(model, "attn_cfg", None), "window", 0)
        codebooks = getattr(getattr(model, "cfg", None), "n_codebooks", 0)
        self.batched = (scfg.batched and not codebooks
                        and all(hasattr(model, m) for m in _BATCHED_API))
        if scfg.batched and not self.batched:
            _downgrade(
                "batched serving requested but this model "
                + ("has a multi-codebook head" if codebooks
                   else "lacks the stacked-cache API")
                + " — falling back to the slot-wise decode loop")
        # windowed/recurrent archs hold O(window)/O(1) state: prompt length
        # is not bounded by the cache, and there is no context-limit retire
        self.ctx_unbounded = bool(getattr(model, "unbounded_context", False))
        kv_dtype = ccfg.resolved_kv_dtype
        # speculative decode: batched-only, needs the verify/rewind API, and
        # the (1+K) verify chunk must fit inside a ring buffer just like a
        # prefill chunk. Greedy AND sampled serving both speculate — sampled
        # acceptance runs rejection resampling (spec_sample_accept)
        self._draft_len = 0
        if scfg.draft_len > 0:
            if not self.batched:
                _downgrade("draft_len > 0 requested but speculation needs "
                           "the batched stacked-cache path — speculative "
                           "decode disabled")
            elif not all(hasattr(model, m) for m in _SPEC_API):
                _downgrade("draft_len > 0 requested but this model lacks "
                           "spec_verify/spec_rewind — speculative decode "
                           "disabled")
            else:
                self._draft_len = (min(scfg.draft_len, window - 1) if window
                                   else scfg.draft_len)
        self.spec = self._draft_len > 0
        self._sampled = scfg.temperature > 0.0
        if mesh is not None and not self.batched:
            raise ValueError(
                "mesh serving requires the batched stacked-cache path "
                "(batched=True and a model exposing write_cache/prefill_extend)")
        # fused decode: flip use_kernel ON in the ccfg the jitted closures
        # below capture, so decode, chunked prefill-extend and spec verify
        # all route linears through the packed-FP4 Pallas matmul (and
        # single-token decode attention through the decode kernel). The
        # weights stay packed codes+scales end-to-end — dequantization
        # happens per-tile inside the kernel, never as a materialized dense
        # tree. Downgrades (don't crash, record + warn) when the
        # prerequisites are missing.
        self.fused = False
        if scfg.fused:
            if ccfg.mode != "serve_fp4":
                _downgrade(
                    f"fused decode requested but ccfg.mode={ccfg.mode!r} — "
                    "the FP4 kernel path needs packed serve_fp4 params "
                    "(codes+scales); running the jnp path")
            elif not self.batched:
                _downgrade(
                    "fused decode requested but the engine runs the "
                    "slot-wise loop — fused dispatch needs the batched "
                    "stacked-cache path; running the jnp path")
            elif mesh is not None:
                _downgrade(
                    "fused decode requested with a device mesh — Pallas "
                    "calls inside GSPMD-partitioned steps are unsupported; "
                    "running the jnp path")
            else:
                self.fused = True
                ccfg = dataclasses.replace(ccfg, use_kernel=True)
                self.ccfg = ccfg
        # paged KV: one fixed-shape page pool per cache leaf, host-owned
        # per-slot block tables, gather-based dense views inside the jitted
        # steps (token-exact with the dense cache). Prefix caching rides on
        # top (the radix tree maps shared prompt prefixes to resident pages).
        self.paged = False
        self.prefix: Optional[RadixPrefixCache] = None
        self.pool: Optional[PagePool] = None
        self._prefix_hits = 0
        self._prefix_lookups = 0
        if scfg.paged or scfg.prefix_cache:
            if not self.batched:
                _downgrade(
                    "paged KV requested but the engine runs the slot-wise "
                    "loop — paging needs the batched stacked-cache path; "
                    "running the dense cache")
            elif mesh is not None:
                _downgrade(
                    "paged KV requested with a device mesh — pool leaves "
                    "have no slot axis to shard over data; running the "
                    "dense cache")
            elif not (getattr(model, "paged_attention", False)
                      and hasattr(model, "init_paged_cache")):
                _downgrade(
                    "paged KV requested but this model's cache state has no "
                    "page-granular layout (ring-buffer / recurrent / "
                    "multi-codebook state) — running the dense cache")
            else:
                self.paged = True
        if self.batched:
            # round the cache length up to a chunk multiple so padded chunk
            # writes never clamp into (and clobber) valid cache entries; a
            # verify pass writes up to draft_len rows past a stream's last
            # position, so speculation adds that much headroom
            c = scfg.prefill_chunk
            need = scfg.max_len + self._draft_len
            self._cache_len = (-(-need // c) * c) if c > 0 else need
            # ring buffers hold exactly the window; a prefill chunk must fit
            # inside the ring so within-chunk writes never collide (see
            # layers.attn_apply)
            self._chunk_cap = window
            if self.paged:
                ps = max(1, scfg.page_size)
                self._page_size = ps
                nb = -(-self._cache_len // ps)
                self._blocks_per_slot = nb
                self._cache_len = nb * ps
                # default pool: every slot can fill all its blocks, plus the
                # reserved trash page — admission can then never deadlock
                # (tree-only pages are always evictable, see serve/prefix.py)
                npages = scfg.num_pages or scfg.max_batch * nb + 1
                self.cache = model.init_paged_cache(scfg.max_batch, npages,
                                                    ps, dtype=kv_dtype)
                self.pool = PagePool(npages)
                self._bt = np.zeros((scfg.max_batch, nb), np.int32)
                self._slot_pages: List[List[int]] = [
                    [] for _ in range(scfg.max_batch)]
                self._watermark_pages = max(
                    1, int(scfg.evict_watermark * (npages - 1)))
                def _copy_page(c_, s_, d_):
                    return model.paged_copy_page(c_, s_, d_)

                # a fresh function per engine (like _copy_page above):
                # jitting the bare module-level function would share one
                # tracing cache across engines, breaking per-engine donation
                # and the retrace guard's compile accounting
                def _reset_pos(c_, s_, p_):
                    return reset_slot_pos(c_, s_, p_)

                self._copy_fn = jax.jit(_copy_page, donate_argnums=(0,))
                self._reset_pos_fn = jax.jit(_reset_pos, donate_argnums=(0,))
                if scfg.prefix_cache:
                    self.prefix = RadixPrefixCache(self.pool, ps,
                                                   copy_page=self._cow_page)
            else:
                self.cache = model.init_cache(scfg.max_batch, self._cache_len,
                                              dtype=kv_dtype)
            self.caches: List[Any] = []   # unused in batched mode
            if mesh is not None:
                # data parallelism only when the slot grid divides the data
                # axes: otherwise activations stay batch-replicated to match
                # the (necessarily replicated) cache — unevenly batch-sharded
                # k/v written into a replicated cache would lower to exactly
                # the masked-add all-reduces the cascade policy forbids
                dsize = 1
                for a in ("pod", "data"):
                    dsize *= mesh.shape.get(a, 1)
                self._batch_axes = (("pod", "data")
                                    if dsize > 1 and scfg.max_batch % dsize == 0
                                    else ())
                # params placed by the TP policy (tied-embedding archs keep
                # a replicated table so the tied head never contracts over a
                # sharded dim); stacked cache shards its slot axis over data
                tied = bool(getattr(getattr(model, "cfg", None),
                                    "tie_embeddings", False))
                self.params = jax.device_put(params, shd.serving_param_shardings(
                    params, mesh, scfg.tp_policy, tied_embed=tied))
                self._cache_pspecs = model.cache_pspecs(self.cache, mesh)
                self.cache = jax.device_put(
                    self.cache, shd.named_shardings(mesh, self._cache_pspecs))
                # pin cache outputs inside every jitted step so the slot-axis
                # placement survives donation round-trips (GSPMD propagation
                # alone is not guaranteed to hand the sharding back)
                pin = lambda c_: jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s),
                    c_, self._cache_pspecs)
            else:
                pin = lambda c_: c_
            self._pin = pin

            # paged steps take the host-owned block table as one extra
            # device arg ({"tokens", "block_table"} batch dict); the jitted
            # computations are otherwise identical — the model gathers dense
            # per-slot K/V views through the table, so shapes stay fixed
            if self.paged:
                def _decode_step(p, t, c_, bt):
                    logits, c2 = model.decode_step(
                        p, {"tokens": t, "block_table": bt}, c_, ccfg)
                    return logits, pin(c2)

                def _extend_step(p, t, c_, bt, n):
                    return model.prefill_extend(
                        p, {"tokens": t, "block_table": bt}, c_, ccfg,
                        n_valid=n)
            else:
                def _decode_step(p, t, c_):
                    logits, c2 = model.decode_step(p, {"tokens": t}, c_, ccfg)
                    return logits, pin(c2)

                def _extend_step(p, t, c_, n):
                    return model.prefill_extend(p, {"tokens": t}, c_, ccfg,
                                                n_valid=n)

                def _write_slot(c_, s_, i):
                    return pin(model.write_cache(c_, s_, i))

                self._write_fn = jax.jit(_write_slot, donate_argnums=(0,))
            self._decode_fn = jax.jit(_decode_step, donate_argnums=(2,))
            self._extend_fn = jax.jit(_extend_step, donate_argnums=(2,))
            if self.spec:
                if self.paged:
                    def _verify_step(p, t, c_, bt):
                        logits, c2, ckpt = model.spec_verify(
                            p, {"tokens": t, "block_table": bt}, c_, ccfg)
                        return logits, pin(c2), ckpt
                else:
                    def _verify_step(p, t, c_):
                        logits, c2, ckpt = model.spec_verify(p, {"tokens": t}, c_, ccfg)
                        return logits, pin(c2), ckpt

                self._verify_fn = jax.jit(_verify_step, donate_argnums=(2,))
                # donate only the cache: checkpoint leaves have chunk-sized
                # shapes no output can reuse (donating them just warns).
                # Paged checkpoints carry their block table, so the rewind
                # signature is mode-independent.
                def _rewind(c_, ck, keep):
                    return pin(model.spec_rewind(c_, ck, keep))

                self._rewind_fn = jax.jit(_rewind, donate_argnums=(0,))
                if self._sampled:
                    # speculative SAMPLING: verify + accept/resample fused
                    # in one jitted dispatch — the acceptance uniforms, the
                    # residual resample and the bonus draw all stay on
                    # device, derived from the step's single fold_in key
                    if self.paged:
                        def _spec_sampled_step(p, t, c_, bt, keff, key):
                            logits, c2, ckpt = model.spec_verify(
                                p, {"tokens": t, "block_table": bt}, c_, ccfg)
                            a, tok = spec_sample_accept(
                                logits, t[:, 1:], keff, key,
                                scfg.temperature, scfg.top_k)
                            return a, tok, pin(c2), ckpt
                    else:
                        def _spec_sampled_step(p, t, c_, keff, key):
                            logits, c2, ckpt = model.spec_verify(
                                p, {"tokens": t}, c_, ccfg)
                            a, tok = spec_sample_accept(
                                logits, t[:, 1:], keff, key,
                                scfg.temperature, scfg.top_k)
                            return a, tok, pin(c2), ckpt

                    self._spec_sample_fn = jax.jit(_spec_sampled_step,
                                                   donate_argnums=(2,))
            if scfg.temperature > 0.0:
                # on-device sampling for the batched grid: decode + categorical
                # draw fused in one jitted step (no per-step host vocab copy)
                if self.paged:
                    def _sampled_step(p, t, c_, bt, key):
                        logits, c2 = model.decode_step(
                            p, {"tokens": t, "block_table": bt}, c_, ccfg)
                        return _sample_tokens(logits[:, -1], key,
                                              scfg.temperature,
                                              scfg.top_k), pin(c2)
                else:
                    def _sampled_step(p, t, c_, key):
                        logits, c2 = model.decode_step(p, {"tokens": t}, c_, ccfg)
                        return _sample_tokens(logits[:, -1], key, scfg.temperature,
                                              scfg.top_k), pin(c2)
                self._sample_fn = jax.jit(_sampled_step, donate_argnums=(2,))
        else:
            self._cache_len = scfg.max_len
            self._chunk_cap = 0
            self.cache = None
            self.caches = [None] * scfg.max_batch
            def _decode_step(p, t, c_):
                return model.decode_step(p, {"tokens": t}, c_, ccfg)

            self._decode_fn = jax.jit(_decode_step)
        if scfg.crest_enabled:
            self._probe_w = self._dense_head_weight()
            self.crest_state = crest.crest_init(self._probe_w.shape[1], scfg.crest_cfg)

    def _dense_head_weight(self):
        """Dense view of the lm_head weight used for CREST BIST probes
        (paper Section 20.6: CREST doubles as POST/BIST with test vectors)."""
        from repro.core import cascade as C
        head = self.params.get("lm_head")
        if head is None:
            return None
        return C.linear_weight(head, self.ccfg)

    # ------------------------------------------------------------ admission
    def submit(self, req: Request):
        # an open-loop driver (or a failover rebuild) pre-stamps the arrival
        # time; only a fresh direct submit takes "now" — re-stamping would
        # erase queueing delay already accrued (on a dead replica, or in a
        # router queue), which is exactly the delay TTFT must charge
        if req.created_at == 0.0:
            req.created_at = self.clock.now()
        self.queue.append(req)

    def _pop_admittable(self) -> Optional[Request]:
        """Next queued request; un-servable prompts — empty, or (for archs
        whose cache grows with context) too long for the slot grid to hold
        with room for even one generated token — are rejected, not crashed
        on / silently clobbered. Windowed/recurrent archs hold O(window)
        state, so no prompt is too long for them."""
        while self.queue:
            req = self.queue.popleft()
            if len(req.prompt) > 0 and (self.ctx_unbounded
                                        or len(req.prompt) < self.scfg.max_len):
                return req
            req.done = True
            req.finished_at = self.clock.now()
            self._rejected += 1
            self._retired.append(req)
        return None

    def _free_slot(self) -> Optional[int]:
        staged = self._staging.slot if self._staging is not None else -1
        for i in range(self.scfg.max_batch):
            if self.slots[i] is None and i != staged:
                return i
        return None

    # ------------------------------------------------------ page management
    def _bt_dev(self) -> jax.Array:
        """Device copy of the host block-table mirror (pushed every step —
        it is a few KB, and host-owned so allocation stays plain Python)."""
        return jnp.asarray(self._bt)

    def _alloc_page(self) -> int:
        """One page off the pool, enforcing the eviction watermark first.

        Under prefix caching, pool pressure above the watermark evicts LRU
        tree-only pages; a genuinely full pool force-evicts one more. With
        the default pool sizing this never raises (slots can always fill)."""
        if self.prefix is not None:
            self.prefix.maybe_evict(self._watermark_pages)
            if self.pool.free_pages == 0:
                self.prefix.evict(1)
        return self.pool.alloc()

    def _cow_page(self, src: int) -> Optional[int]:
        """Radix-cache COW hook: clone physical page ``src`` for a stream
        that diverges mid-page. No eviction here — the tree is mid-walk and
        the LRU victim could be ``src`` itself; a full pool just skips the
        partial-page match (colder, still correct)."""
        if self.pool.free_pages == 0:
            return None
        dst = self.pool.alloc()
        self.cache = self._copy_fn(self.cache, jnp.int32(src), jnp.int32(dst))
        return dst

    def _ensure_pages(self, slot: int, n_tokens: int):
        """Back the slot's first ``n_tokens`` rows with physical pages."""
        ps = self._page_size
        needed = min(-(-n_tokens // ps), self._blocks_per_slot)
        pages = self._slot_pages[slot]
        while len(pages) < needed:
            pg = self._alloc_page()
            self._bt[slot, len(pages)] = pg
            pages.append(pg)

    def _release_slot_pages(self, slot: int):
        """Drop the slot's page refs and point its table at the trash page
        (row 0) so any stale in-flight write/read for this slot is inert.
        Pages the radix tree still holds stay resident for future hits."""
        for pg in self._slot_pages[slot]:
            self.pool.release(pg)
        self._slot_pages[slot] = []
        self._bt[slot, :] = 0

    def _admit_paged(self):
        """Paged admission: prefill the unshared prompt suffix directly into
        the resident grid (no staging cache, no slot write).

        With the prefix cache on, the radix tree resolves the longest cached
        prefix first: matched pages go straight into the slot's block table
        (refcount bump — shared pages are past every write frontier, so
        they are read-only by construction), ``pos`` is reset to the matched
        length, and ONLY the unshared suffix is prefilled — and only the
        suffix is charged against ``token_budget``. The extend runs over
        the full grid with a per-slot ``n_valid`` vector (only the staging
        slot is nonzero); resident streams' rows land above their ``pos``
        (mask-invalid garbage, overwritten when those streams advance) or
        in the trash page, so their decode is untouched — admission stays
        token-exact with the dense engine's staging-cache path."""
        budget = self.scfg.token_budget or 1 << 30
        spent = 0
        while spent < budget:
            if self._staging is None:
                slot = self._free_slot()
                if slot is None:
                    return
                req = self._pop_admittable()
                if req is None:
                    return
                self._admitted(req)
                matched = 0
                assert not self._slot_pages[slot]
                if self.prefix is not None:
                    m = self.prefix.match(req.prompt)
                    self._slot_pages[slot] = list(m.pages)
                    self._bt[slot, :len(m.pages)] = m.pages
                    matched = m.matched
                    self._prefix_hits += m.hit_full
                    self._prefix_lookups += len(req.prompt)
                self.cache = self._reset_pos_fn(self.cache, jnp.int32(slot),
                                                jnp.int32(matched))
                self._staging = _Staging(req, None, matched, slot)
            st = self._staging
            prompt = st.req.prompt
            chunk = self.scfg.prefill_chunk or len(prompt)
            logits = None
            while st.consumed < len(prompt) and spent < budget:
                n = min(chunk, len(prompt) - st.consumed)
                self._ensure_pages(st.slot, st.consumed + n)
                toks = np.zeros((self.scfg.max_batch, chunk), np.int32)
                toks[st.slot, :n] = prompt[st.consumed:st.consumed + n]
                nv = np.zeros((self.scfg.max_batch,), np.int32)
                nv[st.slot] = n
                with self.telemetry.span(telemetry.PREFILL_CHUNK,
                                         uid=st.req.uid, n=n):
                    logits, self.cache = self._extend_fn(
                        self.params, jnp.asarray(toks), self.cache,
                        self._bt_dev(), jnp.asarray(nv))
                self.telemetry.count(telemetry.ADMIT, prompt_tokens=n)
                st.consumed += n
                spent += n                  # unshared suffix only
            if st.consumed < len(prompt):
                return                      # budget exhausted mid-prompt
            nxt = self._first_token(st.req, logits[st.slot, -1])
            self._commit_token(st.req, nxt)
            self.slots[st.slot] = st.req
            if self.prefix is not None:
                # publish the freshly prefilled full pages for future hits
                self.prefix.insert(prompt, self._slot_pages[st.slot])
            if self.spec:
                self._spec_ctx[st.slot] = (
                    st.req.prompt.tolist()
                    + st.req.tokens_out[st.req.prompt_carried:])
            self._staging = None
            self._retire_if_done(st.req, st.slot, nxt)

    def _admit_batched(self):
        """Spend up to ``token_budget`` prompt tokens on (chunked) prefill."""
        budget = self.scfg.token_budget or 1 << 30
        spent = 0
        while spent < budget:
            if self._staging is None:
                slot = self._free_slot()
                if slot is None:
                    return
                req = self._pop_admittable()
                if req is None:
                    return
                self._admitted(req)
                sub = self.model.init_cache(1, self._cache_len,
                                            dtype=self.ccfg.resolved_kv_dtype)
                self._staging = _Staging(req, sub, 0, slot)
            st = self._staging
            prompt = st.req.prompt
            chunk = self.scfg.prefill_chunk or len(prompt)
            if self._chunk_cap:
                chunk = min(chunk, self._chunk_cap)
            logits = None
            while st.consumed < len(prompt) and spent < budget:
                n = min(chunk, len(prompt) - st.consumed)
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :n] = prompt[st.consumed:st.consumed + n]
                with self.telemetry.span(telemetry.PREFILL_CHUNK,
                                         uid=st.req.uid, n=n):
                    logits, st.cache = self._extend_fn(
                        self.params, jnp.asarray(toks), st.cache, jnp.int32(n))
                self.telemetry.count(telemetry.ADMIT, prompt_tokens=n)
                st.consumed += n
                spent += n
            if st.consumed < len(prompt):
                return                      # budget exhausted mid-prompt
            nxt = self._first_token(st.req, logits[0, -1])
            self._commit_token(st.req, nxt)
            with self.telemetry.span(telemetry.SLOT_WRITE, uid=st.req.uid,
                                     slot=st.slot):
                self.cache = self._write_fn(self.cache, st.cache,
                                            jnp.int32(st.slot))
            self.slots[st.slot] = st.req
            if self.spec:
                # seed the slot's draft context with the visible stream
                # (prompt already contains failover-carried tokens)
                self._spec_ctx[st.slot] = (
                    st.req.prompt.tolist()
                    + st.req.tokens_out[st.req.prompt_carried:])
            self._staging = None
            # the prefill-generated token may already end the stream
            self._retire_if_done(st.req, st.slot, nxt)

    def _admit_slotwise(self):
        for i in range(self.scfg.max_batch):
            if self.slots[i] is None and self.queue:
                req = self._pop_admittable()
                if req is None:
                    return
                self._admitted(req)
                toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
                with self.telemetry.span(telemetry.PREFILL_CHUNK, uid=req.uid,
                                         n=len(req.prompt)):
                    logits, cache = self.model.prefill(
                        self.params, {"tokens": toks}, self.ccfg,
                        max_len=self.scfg.max_len)
                self.telemetry.count(telemetry.ADMIT,
                                     prompt_tokens=len(req.prompt))
                nxt = self._first_token(req, logits[0, -1])
                self._commit_token(req, nxt)
                self.slots[i] = req
                self.caches[i] = cache
                # the prefill-generated token may already end the stream
                self._retire_if_done(req, i, nxt)

    def _admitted(self, req: Request):
        """Stamp the end of ``req``'s admission wait."""
        req.admitted_at = self.clock.now()
        wait = req.admitted_at - req.created_at
        self._admission_waits.append(wait)
        self.telemetry.count(telemetry.ADMIT, requests=1, wait_s=wait)

    def _first_token(self, req: Request, row) -> int:
        """The pick after a prefill: the host waits here for the extend."""
        with self.telemetry.span(telemetry.FIRST_TOKEN, uid=req.uid):
            return self._pick(row)

    def _admit(self):
        with self.telemetry.span(telemetry.ADMIT):
            if self.paged:
                self._admit_paged()
            elif self.batched:
                self._admit_batched()
            else:
                self._admit_slotwise()

    # --------------------------------------------------------------- decode
    def _active(self):
        return [i for i, r in enumerate(self.slots) if r is not None]

    def _next_sample_key(self):
        """One counter for every sampling site: fold_in(seed, draw index)."""
        key = jax.random.fold_in(self._sample_key, self._sample_step)
        self._sample_step += 1
        return key

    def _pick(self, row) -> int:
        """Next token from a (V,) logits row (admission / slot-wise path).
        Both argmax and sampling stay ON DEVICE — the admission and
        slot-wise modes share the batched grid's seeded-categorical RNG
        discipline (same fold_in counter), so no path ever copies a vocab
        row to host or keeps a second host-side RNG stream."""
        if self.scfg.temperature <= 0.0:
            return int(jnp.argmax(row))
        return int(self._pick_fn(jnp.asarray(row), self._next_sample_key()))

    def _commit_token(self, req: Request, tok: int):
        """Append a committed token WITH its telemetry: the clock reading at
        commit time and — on the stream's first token — ``first_token_at``.
        A failover clone arrives with carried tokens/timestamps, so the
        first-token stamp only ever fires once per client-visible stream
        (the dead replica's TTFT is the stream's TTFT)."""
        req.tokens_out.append(tok)
        req.token_times.append(self.clock.now())
        if req.first_token_at == 0.0:
            req.first_token_at = req.token_times[-1]

    def _retire_if_done(self, req: Request, i: int, nxt: int):
        # cache usage: prompt + tokens emitted since (carried ones are
        # already inside the prompt — failover clones)
        used = len(req.prompt) + len(req.tokens_out) - req.prompt_carried
        if (len(req.tokens_out) >= req.max_new_tokens
                or nxt == self.scfg.eos_id
                # context limit: the next write would fall outside the cache
                # (never fires for windowed/recurrent archs — ring buffers
                # wrap and recurrent state is O(1))
                or (not self.ctx_unbounded and used >= self.scfg.max_len)):
            req.done = True
            req.finished_at = self.clock.now()
            self._retired.append(req)
            self.slots[i] = None
            if self.paged:
                self._release_slot_pages(i)
            if not self.batched:
                self.caches[i] = None

    def _decode_batched(self, active: List[int]) -> int:
        toks = np.zeros((self.scfg.max_batch, 1), np.int32)
        for i in active:
            req = self.slots[i]
            toks[i, 0] = req.tokens_out[-1]
            if self.paged:
                # the pending token writes at row used-1; back it with a page
                self._ensure_pages(i, len(req.prompt) + len(req.tokens_out)
                                   - req.prompt_carried)
        bt = (self._bt_dev(),) if self.paged else ()
        if self.scfg.temperature <= 0.0:
            logits, self.cache = self._decode_fn(self.params, jnp.asarray(toks),
                                                 self.cache, *bt)
            picks = jnp.argmax(logits[:, -1], axis=-1)
        else:
            # on-device sampling: one fused decode+categorical dispatch; the
            # per-row Gumbel noise is positional (a function of key + slot
            # index), so active rows never depend on garbage-slot contents
            picks, self.cache = self._sample_fn(self.params, jnp.asarray(toks),
                                                self.cache, *bt,
                                                self._next_sample_key())
        with self.telemetry.span(telemetry.DECODE_SYNC):
            nxt = np.asarray(picks)
        produced = 0
        with self.telemetry.span(telemetry.COMMIT):
            for i in active:
                req = self.slots[i]
                tok = int(nxt[i])
                self._commit_token(req, tok)
                produced += 1
                self._retire_if_done(req, i, tok)
        return produced

    def _decode_spec(self, active: List[int]) -> int:
        """One speculative engine step: draft up to K tokens per slot
        (prompt lookup over the slot's own stream, reporting the per-slot
        effective draft length ``k_eff``), score all K+1 positions in ONE
        batched verify pass, commit the accepted prefix plus a final
        bonus/resampled token, then rewind each slot's cache to its accept
        boundary.

        Greedy (``temperature <= 0``): accept the longest real-draft prefix
        matching the model's own argmax — token-exact with plain greedy
        decode. Sampled: the fused verify+accept step runs rejection
        resampling on device (``spec_sample_accept``) — every committed
        token is distributed exactly as plain sampled decode. Padded
        proposals (positions >= ``k_eff``) are never scored as real in
        either mode: under sampling a padded token was never drawn from q
        (scoring it would corrupt the acceptance law), and under greedy a
        padded 0 could spuriously match a legitimate argmax-0 token."""
        k = self._draft_len
        toks = np.zeros((self.scfg.max_batch, k + 1), np.int32)
        keff = np.zeros(self.scfg.max_batch, np.int32)
        with self.telemetry.span(telemetry.DRAFT):
            for i in active:
                # the draft context is the slot's visible stream (prompt —
                # which already contains failover-carried tokens — plus
                # every token emitted since), maintained incrementally; the
                # drafter scans at most the trailing ``ngram_lookback``
                # tokens of it
                ctx = self._spec_ctx[i]
                toks[i, 0] = ctx[-1]               # == tokens_out[-1], pending
                toks[i, 1:], keff[i] = ngram_propose(
                    np.asarray(ctx[-self.scfg.ngram_lookback:], np.int32),
                    k, self.scfg.ngram_max)
                if self.paged:
                    # the verify chunk writes rows used-1 .. used-1+K; rows
                    # past the slot's capacity land in the trash page,
                    # matching the dense path's headroom semantics
                    req = self.slots[i]
                    self._ensure_pages(i, len(req.prompt) + len(req.tokens_out)
                                       - req.prompt_carried + k)
        bt = (self._bt_dev(),) if self.paged else ()
        if self._sampled:
            # ONE counter draw per engine step (the plain sampled step's
            # discipline); accept/resample/bonus randomness derives from it
            a_dev, fin_dev, self.cache, ckpt = self._spec_sample_fn(
                self.params, jnp.asarray(toks), self.cache, *bt,
                jnp.asarray(keff), self._next_sample_key())
            with self.telemetry.span(telemetry.DECODE_SYNC):
                acc = np.asarray(a_dev)
                fin = np.asarray(fin_dev)
        else:
            logits, self.cache, ckpt = self._verify_fn(
                self.params, jnp.asarray(toks), self.cache, *bt)
            picks = jnp.argmax(logits, axis=-1)
            with self.telemetry.span(telemetry.DECODE_SYNC):
                greedy = np.asarray(picks)              # (B, K+1)
        keep = np.zeros(self.scfg.max_batch, np.int32)
        produced = 0
        with self.telemetry.span(telemetry.COMMIT):
            for i in active:
                req = self.slots[i]
                if self._sampled:
                    a = int(acc[i])
                    # accepted drafts verbatim, then the resampled/bonus token
                    seq = [int(toks[i, j + 1]) for j in range(a)] + [int(fin[i])]
                else:
                    a = 0
                    while a < keff[i] and greedy[i, a] == toks[i, a + 1]:
                        a += 1
                    seq = [int(greedy[i, j]) for j in range(a + 1)]
                keep[i] = a + 1                     # accepted drafts + pending token
                self._spec_slot_steps += 1
                # commit one token at a time so eos / max_new / context-limit
                # retirement fires at EXACTLY the token where plain decode
                # would have stopped
                delivered = 0
                ctx = self._spec_ctx[i]
                for tok in seq:
                    self._commit_token(req, tok)
                    ctx.append(tok)
                    delivered += 1
                    self._retire_if_done(req, i, tok)
                    if req.done:
                        break
                # acceptance counts only DELIVERED drafts (retirement may truncate
                # mid-acceptance), keeping tokens/step/slot = accepted_per_step + 1
                self._accepted_drafts += delivered - 1
                produced += delivered
                lb = self.scfg.ngram_lookback
                if len(ctx) > 2 * lb:               # drafter only reads the tail
                    del ctx[:len(ctx) - lb]
        # roll every slot back to its accept boundary (inactive slots:
        # keep=0 — a full rewind, restoring the pre-verify cache)
        self.cache = self._rewind_fn(self.cache, ckpt, jnp.asarray(keep))
        return produced

    def _decode_slotwise(self, active: List[int]) -> int:
        produced = 0
        for i in active:
            req = self.slots[i]
            tok = jnp.asarray([[req.tokens_out[-1]]], jnp.int32)
            logits, self.caches[i] = self._decode_fn(self.params, tok, self.caches[i])
            nxt = self._pick(logits[0, -1] if logits.ndim == 3 else logits[0, -1, 0])
            self._commit_token(req, nxt)
            produced += 1
            self._retire_if_done(req, i, nxt)
        return produced

    @contextlib.contextmanager
    def _sharded_scope(self):
        """Mesh + activation-policy scope for every on-device call.

        Jit tracing happens lazily at first dispatch, so the mesh context
        (bare-``PartitionSpec`` constraints need it) and the activation
        policy (the ``constrain_*`` hooks inside model code) must surround
        the CALLS, not the ``jax.jit`` constructions. The policy is cleared
        on exit so an unsharded engine in the same process — a failover
        survivor, the slot-wise parity baseline — never traces under a
        leftover mesh discipline.
        """
        if self.mesh is None:
            yield
            return
        shd.set_activation_policy(self.mesh, self._act_policy,
                                  batch_axes=self._batch_axes)
        try:
            with self.mesh:
                yield
        finally:
            shd.clear_activation_policy()

    def step(self) -> int:
        """One engine step; returns number of decode tokens produced."""
        tel = self.telemetry
        with tel.step(active=len(self._active()), queued=len(self.queue)), \
                self._sharded_scope():
            self._admit()
            active = self._active()
            if not active:
                return 0
            with tel.span(telemetry.DECODE, active=len(active)):
                t0 = self.clock.now()
                self._steps += 1
                if (self.scfg.crest_enabled
                        and self._steps % self.scfg.crest_every == 0):
                    self._crest_probe()
                produced = (self._decode_spec(active) if self.spec
                            else self._decode_batched(active) if self.batched
                            else self._decode_slotwise(active))
                self.step_times.append(self.clock.now() - t0)
            tel.count(telemetry.DECODE, tokens=produced, slot_steps=len(active))
            self._decode_tokens += produced
            return produced

    # ----------------------------------------------------- contract auditor
    def step_closures(self) -> Dict[str, dict]:
        """Every jitted step closure this engine constructed, by name:
        ``{"fn", "donates_cache", "takes_params"}`` — the registry
        ``repro.analysis.contract.audit_engine`` lowers and audits, and
        ``analysis.retrace`` reads compile counts from. Built on demand
        (by ``getattr`` over the mode-dependent attributes) so it is
        always in sync with what ``__init__`` actually constructed."""
        assert self.batched, "step closures exist only on the batched engine"
        reg: Dict[str, dict] = {}

        def _add(name, attr, donates_cache=True, takes_params=True):
            fn = getattr(self, attr, None)
            if fn is not None:
                reg[name] = {"fn": fn, "donates_cache": donates_cache,
                             "takes_params": takes_params}

        _add("decode", "_decode_fn")
        _add("extend", "_extend_fn")
        _add("write", "_write_fn", takes_params=False)
        _add("verify", "_verify_fn")
        _add("rewind", "_rewind_fn", takes_params=False)
        _add("spec_sample", "_spec_sample_fn")
        _add("sample", "_sample_fn")
        _add("copy_page", "_copy_fn", takes_params=False)
        _add("reset_pos", "_reset_pos_fn", takes_params=False)
        if getattr(getattr(self.model, "cfg", None), "vocab", 0):
            _add("pick", "_pick_fn", donates_cache=False, takes_params=False)
        return reg

    def _step_example_args(self, name: str) -> tuple:
        """Arguments shaped exactly like what ``step()`` dispatches for one
        named closure, for AOT lowering. Token grids are zeros (values are
        irrelevant to the lowered program); the live params/cache carry
        their real placement, so a mesh engine lowers the real sharded
        step. The rewind checkpoint comes from ``eval_shape`` over the
        verify closure — shape-faithful without running a verify pass
        (which would consume the donated cache)."""
        B = self.scfg.max_batch
        bt = (self._bt_dev(),) if self.paged else ()
        key = jax.random.fold_in(self._sample_key, 0)
        if name == "decode":
            return (self.params, jnp.zeros((B, 1), jnp.int32),
                    self.cache) + bt
        if name == "sample":
            return (self.params, jnp.zeros((B, 1), jnp.int32),
                    self.cache) + bt + (key,)
        if name in ("verify", "spec_sample"):
            toks = jnp.zeros((B, self._draft_len + 1), jnp.int32)
            if name == "verify":
                return (self.params, toks, self.cache) + bt
            return (self.params, toks, self.cache) + bt \
                + (jnp.zeros((B,), jnp.int32), key)
        if name == "extend":
            chunk = self.scfg.prefill_chunk or self.scfg.max_len
            if self._chunk_cap:
                chunk = min(chunk, self._chunk_cap)
            if self.paged:
                return (self.params, jnp.zeros((B, chunk), jnp.int32),
                        self.cache) + bt + (jnp.zeros((B,), jnp.int32),)
            staging = self.model.init_cache(
                1, self._cache_len, dtype=self.ccfg.resolved_kv_dtype)
            return (self.params, jnp.zeros((1, chunk), jnp.int32), staging,
                    jnp.int32(chunk))
        if name == "write":
            staging = self.model.init_cache(
                1, self._cache_len, dtype=self.ccfg.resolved_kv_dtype)
            return (self.cache, staging, jnp.int32(0))
        if name == "rewind":
            ckpt = jax.eval_shape(self._verify_fn,
                                  *self._step_example_args("verify"))[2]
            return (self.cache, ckpt, jnp.zeros((B,), jnp.int32))
        if name in ("copy_page", "reset_pos"):
            return (self.cache, jnp.int32(0), jnp.int32(0))
        if name == "pick":
            vocab = int(self.model.cfg.vocab)
            return (jnp.zeros((vocab,), jnp.float32), key)
        raise KeyError(f"unknown step closure {name!r}")

    def lower_step(self, name: str):
        """AOT lower + compile one step closure against the live params/
        cache placement; returns jax's compiled object (``.as_text()`` for
        the HLO). AOT compilation does not touch the jit dispatch cache,
        so auditing composes with the retrace guard."""
        entry = self.step_closures()[name]
        with self._sharded_scope():
            args = self._step_example_args(name)
            return entry["fn"].lower(*args).compile()

    def call_step(self, name: str, *args):
        """Dispatch one named step closure on caller-supplied arguments under
        the engine's mesh scope — for checks that drive a step with fixed
        inputs (teacher forcing) outside the scheduler. The closure traces
        exactly as ``step()`` would, so its compiled program is shared."""
        with self._sharded_scope():
            return self.step_closures()[name]["fn"](*args)

    def decode_step_hlo(self, which: str = "decode") -> str:
        """Compiled HLO of a batched serving step against the live params/
        cache placement — the executable form of the paper's interconnect
        claim: under ``tp_policy='cascade'`` this text contains zero
        partial-sum all-reduce (``benchmarks/hlo_analysis.
        partial_sum_allreduces``), under ``megatron`` it does not.

        ``which``: 'decode' (one-token step) or 'verify' (the speculative
        (1+K)-position verify pass; requires ``draft_len > 0``). With
        ``temperature > 0`` both forms lower the FUSED sampled computation
        the engine actually dispatches — the sampled decode step, and the
        sampled verify+accept/resample step (whose K+1 logit rows are
        pinned replicated before top-k/softmax/Gumbel, so speculative
        sampling obeys the same zero-partial-sum-AR invariant) — not the
        unused greedy ones.
        """
        assert self.batched, "decode_step_hlo requires the batched engine"
        if which == "verify":
            assert self.spec, "verify HLO requires draft_len > 0"
            return self.lower_step(
                "spec_sample" if self._sampled else "verify").as_text()
        return self.lower_step(
            "sample" if self._sampled else "decode").as_text()

    # ------------------------------------------------------------- failover
    def evict(self, i: int) -> Optional[Request]:
        """Free slot i and return its request. The stacked cache slot simply
        becomes garbage — decode state is reconstructible from the prompt +
        emitted tokens (idempotent regenerate), so nothing else to save."""
        req = self.slots[i]
        self.slots[i] = None
        if self.paged:
            self._release_slot_pages(i)
        if not self.batched:
            self.caches[i] = None
        return req

    def abort_in_flight(self) -> List[Request]:
        """Evict every resident/staging request (replica death path)."""
        out = [r for r in (self.evict(i) for i in self._active()) if r is not None]
        if self._staging is not None:
            if self.paged:
                self._release_slot_pages(self._staging.slot)
            out.append(self._staging.req)
            self._staging = None
        return out

    def busy(self) -> bool:
        return bool(self.queue) or self._staging is not None or bool(self._active())

    def load(self) -> int:
        """Queued + resident + mid-prefill work (dispatch balancing input)."""
        return (len(self.queue) + sum(r is not None for r in self.slots)
                + (self._staging is not None))

    # ---------------------------------------------------------------- crest
    def _crest_probe(self):
        """BIST probe wave (paper Section 20.6): run the CREST-protected
        matmul on the lm_head weight with pseudo-random test activations;
        detected faults accumulate in ``crest_state`` and are repaired via
        spare recomputation. ``fault_mask`` lets demos inject defects."""
        if self._probe_w is None:
            return
        key = jax.random.PRNGKey(self._steps)
        x = jax.random.normal(key, (4, self._probe_w.shape[0]), jnp.float32)
        _, self.crest_state = crest.crest_matmul(
            x, self._probe_w.astype(jnp.float32), self.crest_state,
            self.scfg.crest_cfg, self.fault_mask)

    def crest_report(self) -> dict:
        if self.crest_state is None:
            return {}
        return {"confirmed_faults": int(self.crest_state.confirmed_faults.sum()),
                "repaired": int(self.crest_state.n_repaired)}

    # -------------------------------------------------------------- metrics
    @property
    def effective_mode(self) -> str:
        """The decode path this engine ACTUALLY runs (downgrades included):
        '{spec|batched|slotwise}-{greedy|sampled}[-fused][-paged]'. Benches
        and tests assert on this instead of trusting the requested config."""
        decode = ("spec" if self.spec
                  else "batched" if self.batched else "slotwise")
        mode = f"{decode}-{'sampled' if self._sampled else 'greedy'}"
        if self.fused:
            mode += "-fused"
        if self.paged:
            mode += "-paged"
        return mode

    @staticmethod
    def latency_percentiles(requests) -> dict:
        """Per-request latency percentiles over finished requests.

        TTFT = first committed token minus ``created_at`` (the ARRIVAL
        time under an open-loop driver — queueing delay is charged here).
        Inter-token gaps are consecutive differences of each request's
        ``token_times``; under speculative decode a whole accepted run
        commits at one instant, so its intra-run gaps are honestly 0 and
        the step-boundary gap carries the verify-pass cost. Requests that
        never produced a token (rejected/shed) contribute nothing."""
        ttfts = [r.first_token_at - r.created_at for r in requests
                 if r.first_token_at > 0.0]
        gaps = [b - a for r in requests
                for a, b in zip(r.token_times, r.token_times[1:])]
        tt = np.asarray(ttfts, np.float64)
        gp = np.asarray(gaps, np.float64)
        return {
            "ttft_p50_s": float(np.percentile(tt, 50)) if tt.size else 0.0,
            "ttft_p99_s": float(np.percentile(tt, 99)) if tt.size else 0.0,
            "inter_token_p50_s": float(np.percentile(gp, 50)) if gp.size else 0.0,
            "inter_token_p99_s": float(np.percentile(gp, 99)) if gp.size else 0.0,
        }

    def metrics(self) -> dict:
        """Throughput/latency counters for the dashboard & benchmarks."""
        st = np.asarray(self.step_times, np.float64)
        phases = self.telemetry.snapshot()
        return {
            **self.latency_percentiles(self._retired),
            "batched": self.batched,
            "effective_mode": self.effective_mode,
            "downgrades": list(self.downgrades),
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "tp_policy": self.tp_policy if self.mesh is not None else None,
            "spec": self.spec,
            "fused": self.fused,
            "paged": self.paged,
            "page_size": self._page_size if self.paged else 0,
            "prefix_cache": self.prefix is not None,
            "prefix_hits": self._prefix_hits,
            "prefix_lookups": self._prefix_lookups,
            # fraction of submitted prompt tokens served from resident pages
            "prefix_hit_rate": (self._prefix_hits / self._prefix_lookups
                                if self._prefix_lookups else 0.0),
            "pages_in_use": self.pool.pages_in_use if self.paged else 0,
            "pages_total": self.pool.num_pages - 1 if self.paged else 0,
            "evictions": self.prefix.evictions if self.prefix is not None else 0,
            "draft_len": self._draft_len,
            "draft_tokens_accepted": self._accepted_drafts,
            # mean drafted tokens accepted per (slot, step); +1 bonus token
            # always commits, so tokens/step/slot = accepted_per_step + 1
            "accepted_per_step": (self._accepted_drafts / self._spec_slot_steps
                                  if self._spec_slot_steps else 0.0),
            "steps": int(st.size),
            "decode_tokens": self._decode_tokens,
            "admission_wait_s_mean": (float(np.mean(self._admission_waits))
                                      if self._admission_waits else 0.0),
            "step_time_p50_s": float(np.percentile(st, 50)) if st.size else 0.0,
            "step_time_p99_s": float(np.percentile(st, 99)) if st.size else 0.0,
            "requests_finished": len(self._retired) - self._rejected,
            "requests_rejected": self._rejected,
            # calls, seconds and work counts of each engine phase
            "phases": phases,
            "compiles": int(phases.get(telemetry.COMPILE, {}).get("calls", 0)),
        }

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        n0 = len(self._retired)
        for _ in range(max_steps):
            self.step()
            if not self.busy():
                break
        return self._retired[n0:]

    # ----------------------------------------------------- straggler guard
    def straggler_p99(self) -> float:
        if not self.step_times:
            return 0.0
        return float(np.percentile(np.asarray(self.step_times), 99))
