"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. Only ``jax.profiler.ProfileData`` is used.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line holds
one event per HLO instruction executed, named by the instruction's text
(``%cascade_matmul.3 = bf16[8,4096]{...} custom-call(...), ...``), of which
the reduction keeps the instruction's name (``cascade_matmul.3``); the
``XLA Modules`` line holds one event per program executed
(``jit__decode_step(<id>)``). Host planes hold the host threads' spans, the
benchmark's own ``TraceAnnotation`` among them.

A control-flow instruction (``while``, ``conditional``, ``call``) spans the
instructions it runs, so it counts towards busy time (a union) but not in
the breakdown by instruction.

Instructions are clipped to the traced window (the span of the annotation
``WINDOW_SPAN`` when the trace holds it, else the extent of the device
ops and programs); a program run counts only when it lies wholly inside it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

#: the benchmark's host span around the traced part of the window
WINDOW_SPAN = "bench.traced_window"
#: opcodes of collectives (an instruction's name starts with its opcode)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")

#: names of instructions that only run others (their time is their body's)
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?")

Interval = Tuple[int, int]          # (start_ns, end_ns)


def instruction_name(text: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...), ...`` -> ``fusion.12``."""
    m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", text)
    return m.group(1) if m else text


@dataclasses.dataclass
class DeviceOps:
    """One device's executed instructions and programs in the window."""
    ops: List[Tuple[str, int, int]]       # (instruction, start_ns, end_ns)
    modules: List[Tuple[str, int, int]]   # (program, start_ns, end_ns)


@dataclasses.dataclass
class Reduced:
    window: Interval
    devices: Dict[int, DeviceOps]          # device id -> its ops
    host: List[Tuple[str, int, int]]       # host spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _device_id(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def _clip(s: int, e: int, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def reduce_xplane(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    raw: Dict[int, DeviceOps] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            d = raw.setdefault(dev, DeviceOps([], []))
            for line in plane.lines:
                into = {"XLA Ops": d.ops, "XLA Modules": d.modules}.get(line.name)
                if into is None:
                    continue
                name = instruction_name if into is d.ops else str
                for ev in line.events:
                    s = int(ev.start_ns)
                    into.append((name(ev.name), s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((ev.name, s, s + int(ev.duration_ns)))
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if spans:
        window = spans[0]
    else:
        ends = [(s, e) for d in raw.values() for _, s, e in d.ops + d.modules]
        window = (min(s for s, _ in ends), max(e for _, e in ends)) if ends else (0, 0)
    devices = {}
    for dev, d in raw.items():
        ops = [(n, *c) for n, s, e in d.ops if (c := _clip(s, e, window))]
        # whole program runs only, so that a count of runs and the time of
        # their instructions cover the same work
        mods = [(n, s, e) for n, s, e in d.modules
                if window[0] <= s and e <= window[1]]
        devices[dev] = DeviceOps(ops, mods)
    return Reduced(window, devices, host)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(red: Reduced, device: int) -> float:
    """Seconds in which any instruction ran on ``device``."""
    return sum(e - s for s, e in union(
        [(s, e) for _, s, e in red.devices[device].ops])) * 1e-9


def mean_busy_s(red: Reduced) -> float:
    """Busy seconds averaged over the devices in the trace."""
    return sum(busy_s(red, d) for d in red.devices) / len(red.devices)


def idle_gaps(red: Reduced, device: int) -> List[Interval]:
    """Intervals of the window in which nothing ran on ``device``."""
    gaps, t = [], red.window[0]
    for s, e in union([(s, e) for _, s, e in red.devices[device].ops]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if red.window[1] > t:
        gaps.append((t, red.window[1]))
    return gaps


def host_activity(red: Reduced, at_ns: int) -> str:
    """The innermost host span (the shortest one) that covers ``at_ns``,
    other than the window's own."""
    best = None
    for n, s, e in red.host:
        if s <= at_ns < e and n != WINDOW_SPAN and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "no host span"


def module_runs(red: Reduced, device: int, prefix: str) -> List[Interval]:
    """Executions of programs whose name starts with ``prefix``."""
    return [(s, e) for n, s, e in red.devices[device].modules if n.startswith(prefix)]


def op_seconds(red: Reduced, device: int, names: set,
               within: Optional[List[Interval]] = None) -> float:
    """Summed device time of the instructions named in ``names`` (inside the
    ``within`` intervals, when given)."""
    runs = sorted(within or [])
    starts = [a for a, _ in runs]
    total = 0
    for n, s, e in red.devices[device].ops:
        if n not in names:
            continue
        if within is not None:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
        total += e - s
    return total * 1e-9


def collective_seconds(red: Reduced, device: int) -> float:
    ivs = [(s, e) for n, s, e in red.devices[device].ops if n.startswith(COLLECTIVES)]
    return sum(e - s for s, e in union(ivs)) * 1e-9


def breakdown(red: Reduced, device: int = 0, top: int = 10) -> dict:
    """The device instructions that took most time (by instruction, summed
    over its executions) and the longest idle gaps, each named by what the
    host was doing in its middle."""
    by_op: Dict[str, int] = {}
    for n, s, e in red.devices[device].ops:
        if not CONTAINER.fullmatch(n):
            by_op[n] = by_op.get(n, 0) + (e - s)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(red, device), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[host_activity(red, (s + e) // 2), (e - s) * 1e-9]
                          for s, e in gaps]}
