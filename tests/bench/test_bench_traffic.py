"""The benchmark's traffic generator: seeded, and the same set of sizes for
every seed, in an order of the seed's own."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

ROOT = Path(__file__).resolve().parents[2]
#: an open-loop mix, as a later cell's traffic file would give it
POISSON = {"arrival": "poisson", "rate_rps": 0.5, "lead_s": 4.0,
           "prompt_lens": [[512, 1024], [1025, 1792]], "prompt_mix": [0.7, 0.3],
           "output_lens": [[16, 128]], "output_mix": [1.0]}
#: a backlog of the same mixture of long prompts
BACKLOG = dict(POISSON, arrival="backlog", max_retire_per_slot_s=0.5)
INLINE = {"poisson": POISSON, "backlog": BACKLOG}
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")) + sorted(INLINE)


def mix(name):
    if name in INLINE:
        return dict(INLINE[name])
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def sizes(plan, lead):
    due = [-lead] + [p.due_s for p in plan]
    return (sorted(len(p.prompt) for p in plan), sorted(p.max_new_tokens for p in plan),
            sorted(np.diff(due).round(9)))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_trace(name):
    a = T.generate(mix(name), 2 ** 31 + 7, 10.0, 8, 1000)
    b = T.generate(mix(name), 2 ** 31 + 7, 10.0, 8, 1000)
    assert [(p.due_s, p.max_new_tokens, p.prompt.tolist()) for p in a] == \
        [(p.due_s, p.max_new_tokens, p.prompt.tolist()) for p in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    a = T.generate(mix(name), 1, 10.0, 8, 1000)
    b = T.generate(mix(name), 2, 10.0, 8, 1000)
    lead = mix(name).get("lead_s", 0.0)
    assert sizes(a, lead) == sizes(b, lead)
    assert [p.prompt.tolist() for p in a] != [p.prompt.tolist() for p in b]


@pytest.mark.parametrize("name", MIXES)
def test_each_seed_draws_its_own_order(name):
    a, b = (T.generate(mix(name), s, 51.0, 8, 1000) for s in (11, 2 ** 31 + 12))
    assert [(p.due_s, len(p.prompt), p.max_new_tokens) for p in a] != \
        [(p.due_s, len(p.prompt), p.max_new_tokens) for p in b]


@pytest.mark.parametrize("name", sorted(INLINE))
def test_lengths_stay_in_their_ranges(name):
    m = mix(name)
    plan = T.generate(m, 3, 20.0, 4, 500)
    lens = np.array([len(p.prompt) for p in plan])
    assert lens.min() >= 512 and lens.max() <= 1792
    assert abs(np.mean(lens > 1024) - 0.3) < 0.05
    assert all(16 <= p.max_new_tokens <= 128 for p in plan)
    assert all(0 <= t < 500 for p in plan for t in p.prompt)


def test_poisson_arrivals_start_before_the_window():
    m = dict(mix("poisson"), rate_rps=1.0)
    plan = T.generate(m, 4, 20.0, 4, 500)
    due = np.array([p.due_s for p in plan])
    assert due[0] > -m["lead_s"]
    assert (np.diff(due) > 0).all()
    assert due[0] < 0 and due[-1] > 10.0
    assert abs(len(plan) - m["rate_rps"] * (m["lead_s"] + 20.0)) <= 1


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
def test_backlog_staggers_the_first_slots(seed):
    m = mix("decode_saturated")
    plan = T.generate(m, seed, 10.0, 8, 500)
    first = sorted(p.max_new_tokens for p in plan[:8])
    assert first == [80, 160, 240, 320, 400, 480, 560, 640]
    assert sorted(len(p.prompt) for p in plan[:8]) == [76, 100, 124, 148, 172, 196, 220, 244]
    assert all(p.due_s == 0.0 for p in plan)
    assert len(plan) > 8 + 8 * 10 * m["max_retire_per_slot_s"] - 1
