"""How ``correct`` is decided: what the timed path served, against the plain
reference.

Once the window has closed, a sample of the requests it served is drawn
from the seed, with the longest among them: those it finished, and those
still in their slots at the close with the tokens they served so far (a
saturated decode cell's answers run longer than a window). The reference
runs over each prompt with its served tokens, and every served token is
judged by its gap:
the reference's best logit at that position minus the reference's logit of
the served token. Greedy decoding serves the program's own best token, so
the gap is 0 where the two agree and small where rounding flipped a near
tie; a wrong token, a wrong cache row or a skipped layer makes it large.

Compared, each against the limit in ``bench/workloads/<cell>.json``:

* ``max_logit_gap`` - the widest gap over every checked token;
* ``malformed``     - checked requests that served more than their
  ``max_new_tokens``, or finished with fewer (no stop token is
  configured), or served a token outside the vocabulary, limit 0;
* ``unchecked``     - requests of the sample the window did not serve,
  limit 0.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import reference
from bench.traffic import seed_sequence


def attempted_failed(rec) -> tuple:
    """An open-loop cell attempts every request that arrived in the window;
    a backlog cell every request the window served any part of. Failed:
    rejected by admission (done with nothing served)."""
    if rec.cell.traffic["arrival"] == "poisson":
        reqs = rec.arrivals()
    else:
        reqs = [r for r in rec.drive.requests
                if any(rec.in_window(t) for t in r.token_times)
                or rec.in_window(r.admitted_at)]
    failed = sum(r.done and not r.tokens_out for r in reqs)
    return len(reqs), failed


def served(rec) -> list:
    """Requests that served a token inside the window or finished after it
    opened, with what they served."""
    return [r for r in rec.drive.requests if r.tokens_out and (
        (r.done and r.finished_at >= rec.drive.window[0])
        or any(rec.in_window(t) for t in r.token_times))]


def malformed(r, vocab: int) -> bool:
    n = len(r.tokens_out)
    return (n > r.max_new_tokens or (r.done and n != r.max_new_tokens)
            or min(r.tokens_out) < 0 or max(r.tokens_out) >= vocab)


def sample(reqs: list, n: int, seed: int) -> list:
    """The longest request (most tokens served) and ``n - 1`` more drawn
    from the seed."""
    if not reqs:
        return []
    order = sorted(range(len(reqs)), key=lambda i: -len(reqs[i].tokens_out))
    rest = np.random.default_rng(seed_sequence(seed, 4)).permutation(order[1:])
    return [reqs[order[0]]] + [reqs[i] for i in rest[: n - 1]]


def compare(params, cfg: dict, reqs: list, workload: dict, seed: int,
            pick=None) -> dict:
    """The compared numbers, each with its limit. ``pick(params, cfg,
    prompt, served, length)`` puts other tokens in the served ones' place
    (the control); by default the served tokens are judged."""
    chk = workload["check"]
    picked = sample(reqs, chk["requests"], seed)
    widest, tokens, bad = 0.0, 0, 0
    for r in picked:
        if malformed(r, cfg["vocab_size"]):
            bad += 1
            continue
        served = np.asarray(r.tokens_out, np.int64)
        length = workload["max_len"]
        chosen = None if pick is None else pick(params, cfg, r.prompt, served, length)
        gaps = reference.served_gaps(params, cfg, r.prompt, served, length, chosen)
        widest = max(widest, float(np.max(gaps)))
        tokens += len(served)
    print(f"checked {len(picked)} requests, {tokens} served tokens, against "
          f"the float32 reference", file=sys.stderr)
    return {"max_logit_gap": {"value": widest, "limit": chk["max_logit_gap"]},
            "malformed": {"value": bad, "limit": 0},
            "unchecked": {"value": chk["requests"] - len(picked), "limit": 0}}


def verdict(compared: dict, failed: int) -> bool:
    """``correct``: every compared number within its limit, and no request
    failed."""
    return all(c["value"] <= c["limit"] for c in compared.values()) and failed == 0
