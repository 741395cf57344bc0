"""The serving engine's own spans and counters (``repro.serve.telemetry``),
read for the per-layer metrics: its counters over the run's window, and the
device's idle time inside its spans in a trace.

A program without them gives None here, and its readers report nothing.
"""
from __future__ import annotations

from typing import List, Optional

from bench import trace as TR


def names():
    """The program's telemetry module (its span and counter names), or
    None where the program has none."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry


def window_counters(run) -> Optional[dict]:
    """The engine's counters of what ended inside the run's window (the
    engine and the drive read one clock), or None when the process holds
    none for this run: no telemetry, a log that no longer reaches the
    window's start, or the telemetry of another engine (its step count is
    not the drive's)."""
    T = names()
    tel = T.last() if T is not None else None
    if tel is None:
        return None
    a, b = run.drive.window
    w = tel.between(a, b)
    steps = sum(a <= s.start and s.end <= b for s in run.drive.steps)
    if w is None or w.get(T.STEP, {}).get("calls", 0) != steps:
        return None
    return w


def span_idle_s(red: TR.Reduced, name: str, device: int = 0) -> List[float]:
    """For each host span named ``name`` that lies wholly inside the trace's
    window: ``device``'s idle seconds inside it (``idle_gaps`` clipped to
    the span)."""
    gaps = TR.idle_gaps(red, device)
    w0, w1 = red.window
    return [sum(max(0, min(e, b) - max(s, a)) for a, b in gaps) * 1e-9
            for n, s, e in red.host if n == name and w0 <= s and e <= w1]
