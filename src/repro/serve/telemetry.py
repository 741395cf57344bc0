"""Spans and counters of the serving engine.

A span marks one phase of an engine step. It enters
``jax.profiler.TraceAnnotation``, so when a profiler runs the phase lands as
a host span in the same ``.xplane.pb`` as the device's ops, on the
profiler's clock; with no profiler running it costs about a microsecond.
On exit it adds one call and the seconds it took, on the engine's clock, to
that name's counter. ``count`` adds work counts (requests, tokens) at the
same boundary.

``snapshot()`` is a plain dict ``{name: {"calls", "seconds", <items>}}``;
two snapshots subtract (``delta``) to the counters of the time between them.
Every span and count is also kept, stamped with the clock, in a bounded
log, so that ``between(t0, t1)`` gives the same dict for an interval that
is over, as long as the log still reaches back to ``t0``.

The compile counter ``COMPILE`` counts every compilation in the process, of
a jitted function or of an eager op, while the telemetry lives: one call per
cache miss of JAX's in-process compile cache (whether or not the persistent
cache then holds the executable), the seconds of the lowering and of the
backend compile (which holds the persistent cache's retrieval, when that
hits), and, in the log, the name of what was compiled. JAX's listener is
registered once per process.

A child span lies inside its parent: a phase's self time is its span less
its children's. Spans of one request carry its ``uid``.

The routed-work counter ``ROUTED`` (not a span) counts, for each decode step
of a model with routed experts, the work its held experts were given:
``steps`` (1 a step), ``rows`` (its active rows), ``assignments`` (token ->
held-expert assignments of those rows, summed over the MoE layers),
``pairs`` ((MoE layer, held expert) pairs that got at least one of them)
and ``layer_experts`` (MoE layers x held experts, the pairs there are). The
decode step returns which held experts each row went to beside its logits,
and the engine reads it in the same transfer as the step's picks
(``DECODE_SYNC``). A model without experts returns nothing extra from its
decode step and counts nothing here.
"""
from __future__ import annotations

import contextlib
import weakref
from collections import deque
from typing import Dict, List, Optional

import jax

#: one engine step (``ServeEngine.step``), a ``StepTraceAnnotation``
STEP = "serve.step"
#: admission: free slots filled from the queue, prefill included
ADMIT = "serve.admit"
#: one dispatch of the chunked-prefill (extend) program
PREFILL_CHUNK = "serve.prefill_chunk"
#: the pick of a prefilled prompt's first token: the host waits for the extend
FIRST_TOKEN = "serve.first_token"
#: the staging cache written into the slot grid
SLOT_WRITE = "serve.slot_write"
#: the decode half: token grid, dispatch, sync, commit
DECODE = "serve.decode"
#: the host waiting for the decode step's picks
DECODE_SYNC = "serve.decode_sync"
#: committing the picked tokens and retiring finished streams
COMMIT = "serve.commit"
#: n-gram drafting of a speculative step
DRAFT = "serve.draft"
#: routed work of the held experts in each decode step (not a span)
ROUTED = "serve.routed"
#: compilations in the process (not a span)
COMPILE = "jax.compile"

#: spans and counts kept for ``between``
HISTORY = 1 << 16

#: an in-process compile-cache miss: fires once per compilation
_MISS_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: the backend compile, or the persistent cache's retrieval inside it
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_live: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()
_last: Optional["Telemetry"] = None
_listening = False


def _on_event(event: str, seconds: float, **kw) -> None:
    if event == _MISS_EVENT:
        for t in list(_live):
            t._add(COMPILE, calls=1, seconds=seconds,
                   label=str(kw.get("fun_name", "")))
    elif event == _BACKEND_EVENT:
        for t in list(_live):
            t._add(COMPILE, seconds=seconds)


def last() -> Optional["Telemetry"]:
    """The telemetry made last in this process (it outlives its engine), for
    a harness that reads a run's counters once the engine is gone."""
    return _last


def delta(after: dict, before: dict) -> dict:
    """``after - before`` for two snapshots of one telemetry."""
    out = {}
    for name, c in after.items():
        b = before.get(name, {})
        out[name] = {k: v - b.get(k, 0) for k, v in c.items()}
    return out


def _tally(counters: dict, name: str, calls: int, seconds: float,
           items: Optional[dict]) -> None:
    c = counters.setdefault(name, {"calls": 0, "seconds": 0.0})
    c["calls"] += calls
    c["seconds"] += seconds
    for k, v in (items or {}).items():
        c[k] = c.get(k, 0) + v


class Telemetry:
    """The counters of one engine, read from its ``clock``."""

    def __init__(self, clock, history: int = HISTORY):
        global _last, _listening
        self.clock = clock
        self._counters: Dict[str, Dict[str, float]] = {}
        # (clock time, name, calls, seconds, items or None, label or None)
        self._log: deque = deque(maxlen=history)
        self._horizon = float("-inf")   # newest time the log has dropped
        _live.add(self)
        _last = self
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True

    def _add(self, name: str, calls: int = 0, seconds: float = 0.0,
             items: Optional[dict] = None, label: Optional[str] = None) -> None:
        _tally(self._counters, name, calls, seconds, items)
        if len(self._log) == self._log.maxlen:
            self._horizon = self._log[0][0]
        self._log.append((self.clock.now(), name, calls, seconds, items, label))

    @contextlib.contextmanager
    def _timed(self, name: str, annotation):
        t0 = self.clock.now()
        with annotation:
            yield
        self._add(name, calls=1, seconds=self.clock.now() - t0)

    def span(self, name: str, **meta):
        """One phase: a host span in the profiler's trace and a call and
        its seconds in ``name``'s counter."""
        return self._timed(name, jax.profiler.TraceAnnotation(name, **meta))

    def step(self, **meta):
        """The ``STEP`` span, numbered for the profiler's step view."""
        n = int(self._counters.get(STEP, {}).get("calls", 0))
        return self._timed(STEP, jax.profiler.StepTraceAnnotation(
            STEP, step_num=n, **meta))

    def count(self, name: str, **items) -> None:
        """Add work counts (``requests=1``, ``tokens=16``) to ``name``."""
        self._add(name, items=items)

    def snapshot(self) -> dict:
        return {name: dict(c) for name, c in self._counters.items()}

    def between(self, t0: float, t1: float) -> Optional[dict]:
        """The counters of what ended in ``(t0, t1]`` on the clock, or None
        when the log no longer reaches back to ``t0``."""
        if t0 < self._horizon:
            return None
        out: Dict[str, Dict[str, float]] = {}
        for t, name, calls, seconds, items, _ in self._log:
            if t0 < t <= t1:
                _tally(out, name, calls, seconds, items)
        return out

    def labels(self, name: str, t0: float, t1: float) -> List[str]:
        """Labels logged under ``name`` in ``(t0, t1]``: for ``COMPILE``,
        what was compiled."""
        return [label for t, n, _, _, _, label in self._log
                if n == name and label and t0 < t <= t1]
