"""What a run leaves for the metric readers, and the arithmetic they share.

Each ``bench/metrics/<name>.py`` has ``read(run: Record) -> float | None``;
``None`` means the run holds nothing to read for that metric, and the
harness leaves it out of the result line.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import counts


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), or None if empty."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


@dataclasses.dataclass
class Record:
    cell: object                 # bench.cells.Cell
    cfg: dict                    # the model configuration as run
    drive: object                # bench.serving.Drive
    setup_s: float
    peaks: dict                  # one chip's peaks (bench/peaks.json)
    chips: int
    memory_peak_bytes: int
    trace: object = None         # bench.trace.Reduced (traced runs)
    kernels: Dict[str, set] = dataclasses.field(default_factory=dict)
    decode_module: str = "jit__decode_step"

    # ------------------------------------------------------------ window
    @property
    def window_s(self) -> float:
        return self.drive.window[1] - self.drive.window[0]

    def in_window(self, t: float) -> bool:
        return self.drive.in_window(t)

    def arrivals(self) -> list:
        """Requests due inside the window (an open-loop run)."""
        return [r for r, t in zip(self.drive.requests, self.drive.due)
                if self.drive.window[0] <= t < self.drive.window[1]]

    def window_tokens(self) -> int:
        return sum(self.in_window(t) for r in self.drive.requests
                   for t in r.token_times)

    def inter_token_gaps(self) -> List[float]:
        """Gaps between consecutive committed tokens of one request, both
        inside the window."""
        return [b - a for r in self.drive.requests
                for a, b in zip(r.token_times, r.token_times[1:])
                if self.in_window(a) and self.in_window(b)]

    def decode_steps(self, interval=None) -> list:
        a, b = interval or self.drive.window
        return [s for s in self.drive.steps
                if s.decode_s > 0 and a <= s.start and s.end <= b]

    # --------------------------------------------------------------- work
    def step_positions(self, steps: list) -> List[int]:
        """Per decode step: the valid cache rows its active slots attend
        over (a slot holding ``S`` prompt tokens that commits its token
        ``i >= 1`` attends over ``S + i`` rows)."""
        ends = [s.end for s in steps]
        out = [0] * len(steps)
        for r in self.drive.requests:
            for i, t in enumerate(r.token_times[1:], start=1):
                j = bisect.bisect_left(ends, t)
                if j < len(steps) and steps[j].start <= t <= steps[j].end:
                    out[j] += len(r.prompt) + i
        return out

    def decode_work(self, steps: list) -> counts.Work:
        w = counts.Work()
        for s, pos in zip(steps, self.step_positions(steps)):
            w = w + counts.decode_step_work(self.cfg, s.produced, pos, run=self)
        return w

    def prefilled(self) -> list:
        """Requests whose prefill ended (first token) inside the window."""
        return [r for r in self.drive.requests
                if r.first_token_at and self.in_window(r.first_token_at)
                and r.admitted_at >= self.drive.window[0]]

    def prefill_work(self, reqs: list) -> counts.Work:
        chunk = self.cell.config["serve"]["prefill_chunk"]
        w = counts.Work()
        for r in reqs:
            w = w + counts.prefill_work(self.cfg, len(r.prompt), chunk, run=self)
        return w

    @property
    def peak_flops(self) -> float:
        return self.chips * self.peaks["bf16_flops_per_s"]

    @property
    def peak_bw(self) -> float:
        return self.chips * self.peaks["hbm_bytes_per_s"]

    def roofline_s(self, work: counts.Work) -> float:
        return work.roofline_s(self.peak_flops, self.peak_bw)

    # -------------------------------------------------------------- trace
    def devices_traced(self) -> set:
        """Ids of the devices the trace holds (none in an untraced run)."""
        return set(self.trace.devices) if self.trace is not None else set()

    def traced_decode_runs(self, device: int = 0) -> list:
        """Executions of the decode program in the trace."""
        from bench.trace import module_runs
        return module_runs(self.trace, device, self.decode_module)

    def traced_step_mean(self, fn) -> Optional[float]:
        """Mean of ``fn(step, positions)`` over the host's decode steps
        inside the traced interval."""
        steps = self.decode_steps(self.drive.traced)
        if not steps:
            return None
        return float(np.mean([fn(s, p) for s, p in
                              zip(steps, self.step_positions(steps))]))
