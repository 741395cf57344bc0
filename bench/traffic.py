"""The one traffic generator. A traffic mix is a JSON file of parameters
(``bench/traffic/<name>.json``); this module turns it and a seed into a
list of requests with due times.

Two arrival kinds:

* ``backlog`` - closed backlog: every request is due at once, so the queue
  always holds more requests than there are slots. The first ``max_batch``
  requests, which fill the slot grid before the window opens, get
  staggered output budgets (spread evenly over ``(0, output_hi]``) so that
  the grid retires at a steady pace from the first step instead of all at
  once, and prompts at the stratified quantiles of their own.
* ``poisson`` - open loop: exponential inter-arrival gaps at ``rate_rps``,
  starting ``lead_s`` seconds before the window opens.

Steadiness: the sizes and gaps are the stratified quantiles of each
distribution, so every seed serves the same set of sizes (and gaps) and
the seed draws their order, the token ids and the weights: with a few
dozen requests a window, independent draws would change how much work a
window holds from seed to seed. Derived from ``repro.serve.traffic.poisson_trace``
(mixtures of inclusive integer ranges, exponential gaps), kept here so that
the yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of a run: due ``due_s`` seconds after the window opens
    (negative: before it)."""
    uid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def seed_sequence(seed: int, *tag: int) -> np.random.SeedSequence:
    """Any non-negative whole number (larger than 32 bits too) as a seed."""
    return np.random.SeedSequence([int(seed), *tag])


def stratified_lengths(n: int, ranges, mix) -> np.ndarray:
    """``n`` integer lengths from a weighted mixture of inclusive ranges, at
    evenly spaced quantiles: the component counts are fixed by the weights
    (largest remainder) and each component is spread evenly over its range."""
    assert len(ranges) == len(mix) >= 1, (ranges, mix)
    w = np.asarray(mix, np.float64)
    assert (w >= 0).all() and w.sum() > 0, mix
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for j in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[j] += 1
    out = []
    for (lo, hi), c in zip(ranges, counts):
        assert 1 <= lo <= hi, (lo, hi)
        q = (np.arange(c) + 0.5) / max(c, 1)
        out.append(lo + np.floor(q * (hi - lo + 1)).astype(np.int64))
    return np.concatenate(out)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the evenly spaced quantiles of an
    exponential distribution of mean ``1 / rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def request_count(traffic: dict, max_batch: int, seconds: float) -> int:
    """How many requests a run of ``seconds`` needs."""
    if traffic["arrival"] == "poisson":
        return int(math.ceil(traffic["rate_rps"] * (traffic["lead_s"] + seconds)))
    # a backlog holds a few times what the window can retire: every slot
    # retires at most ``max_retire_per_slot_s`` requests a second
    return max_batch + int(math.ceil(
        traffic["max_retire_per_slot_s"] * max_batch
        * (traffic.get("lead_s", 0.0) + seconds)))


def generate(traffic: dict, seed: int, seconds: float, max_batch: int,
             vocab: int) -> List[Planned]:
    """The run's requests in due order."""
    n = request_count(traffic, max_batch, seconds)
    rng = np.random.default_rng(seed_sequence(seed, 1))
    order = np.random.default_rng(seed_sequence(seed, 5))

    def lengths(m, kind):
        return order.permutation(stratified_lengths(
            m, traffic[f"{kind}_lens"], traffic[f"{kind}_mix"]))

    if traffic["arrival"] == "poisson":
        plens, olens = lengths(n, "prompt"), lengths(n, "output")
        due = np.cumsum(order.permutation(
            exponential_gaps(n, traffic["rate_rps"]))) - traffic["lead_s"]
    elif traffic["arrival"] == "backlog":
        due = np.zeros(n)
        out_hi = max(hi for _, hi in traffic["output_lens"])
        k = min(max_batch, n)
        plens = np.concatenate([lengths(k, "prompt"), lengths(n - k, "prompt")])
        olens = np.concatenate([
            order.permutation(np.ceil(out_hi * (np.arange(k) + 1) / k).astype(np.int64)),
            lengths(n - k, "output")])
    else:
        raise ValueError(f"unknown arrival kind {traffic['arrival']!r}")
    return [Planned(uid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                    max_new_tokens=int(olens[i]))
            for i in range(n)]
