"""The serving engine's spans and counters: exact under a virtual clock, on
the profiler's trace as nested host spans, and a compile counter that sees
every compilation in the process."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cascade import CascadeConfig
from repro.models import registry
from repro.serve import telemetry as T
from repro.serve.engine import Request, ServeConfig, ServeEngine
from repro.serve.traffic import VirtualClock

CCFG = CascadeConfig(mode="train", compute_dtype=jnp.float32)
CHUNK = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg, model = registry.load("codeqwen1.5-7b", smoke=True)
    return cfg, model, model.init_params(jax.random.PRNGKey(0), CCFG)


def _engine(tiny_model, clock=None, max_batch=2):
    cfg, model, params = tiny_model
    return ServeEngine(model, params, CCFG,
                       ServeConfig(max_batch=max_batch, max_len=64,
                                   batched=True, prefill_chunk=CHUNK),
                       clock=clock)


def _requests(cfg, lens, max_new=4, uid0=0):
    rng = np.random.default_rng(uid0)
    return [Request(uid=uid0 + i, max_new_tokens=max_new,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32))
            for i, n in enumerate(lens)]


def test_counters_are_exact_under_a_virtual_clock(tiny_model):
    clk = VirtualClock(start=100.0)
    eng = _engine(tiny_model, clk)
    lens = [5, 12, 8, 20, 3]
    reqs = _requests(tiny_model[0], lens)
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.busy():
        eng.step()
        steps += 1
        clk.advance(1.0)
    ph = eng.metrics()["phases"]
    m = eng.metrics()
    assert ph[T.STEP]["calls"] == steps
    assert ph[T.ADMIT]["calls"] == steps
    assert ph[T.ADMIT]["requests"] == len(reqs)
    assert ph[T.ADMIT]["prompt_tokens"] == sum(lens)
    assert ph[T.ADMIT]["wait_s"] == sum(r.admitted_at - r.created_at for r in reqs)
    assert ph[T.PREFILL_CHUNK]["calls"] == sum(-(-n // CHUNK) for n in lens)
    assert ph[T.FIRST_TOKEN]["calls"] == ph[T.SLOT_WRITE]["calls"] == len(reqs)
    assert ph[T.DECODE]["calls"] == len(eng.step_times)
    assert ph[T.DECODE]["tokens"] == m["decode_tokens"]
    assert ph[T.DECODE_SYNC]["calls"] == ph[T.COMMIT]["calls"] == len(eng.step_times)
    # the clock stands still inside a step: every span took 0 s of it
    assert all(c["seconds"] == 0.0 for name, c in ph.items() if name != T.COMPILE)
    # one step's counters, read back from the log, are its snapshot delta
    eng.submit(_requests(tiny_model[0], [6], uid0=10)[0])
    before, t = eng.telemetry.snapshot(), clk.now()
    eng.step()
    one = T.delta(eng.telemetry.snapshot(), before)
    assert eng.telemetry.between(t - 0.5, t + 0.5) == {
        name: c for name, c in one.items() if any(c.values())}
    assert one[T.ADMIT]["requests"] == 1 and one[T.STEP]["calls"] == 1


def test_between_refuses_a_window_the_log_has_dropped():
    clk = VirtualClock()
    tel = T.Telemetry(clk, history=4)
    for _ in range(6):
        clk.advance(1.0)
        tel.count(T.DECODE, tokens=2)
    assert tel.between(0.0, 6.0) is None
    assert tel.between(2.5, 6.0) == {T.DECODE: {"calls": 0, "seconds": 0.0,
                                                "tokens": 8}}


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


def test_spans_nest_in_the_profilers_trace(tiny_model, tmp_path):
    eng = _engine(tiny_model)
    for r in _requests(tiny_model[0], [9, 4]):
        eng.submit(r)
    eng.step()                                  # compiles outside the trace
    eng.submit(_requests(tiny_model[0], [7], uid0=7)[0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.busy():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)

    def named(name):
        return [e for e in ev if e[0] == name]

    def inside(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)

    steps, decodes = named(T.STEP), named(T.DECODE)
    syncs = named(T.DECODE_SYNC)
    assert steps and decodes and syncs
    assert all(inside(d, steps) for d in decodes)
    assert all(inside(s, decodes) for s in syncs)
    assert all(inside(c, decodes) for c in named(T.COMMIT))
    chunks = named(T.PREFILL_CHUNK)
    assert [c[3]["uid"] for c in chunks] == [7]
    assert chunks[0][3]["n"] == 7
    assert all(inside(c, named(T.ADMIT)) for c in chunks + named(T.FIRST_TOKEN))
    assert [s[3]["uid"] for s in named(T.SLOT_WRITE)] == [7]


def test_compile_counter_reads_zero_once_warm(tiny_model):
    cfg = tiny_model[0]
    eng = _engine(tiny_model)
    for r in _requests(cfg, [8, 8]):
        eng.submit(r)
    while eng.busy():
        eng.step()
    warm = eng.telemetry.snapshot()
    for r in _requests(cfg, [8, 8, 8], uid0=3):
        eng.submit(r)
    while eng.busy():
        eng.step()
    after = eng.telemetry.snapshot()
    assert T.delta(after, warm).get(T.COMPILE, {"calls": 0})["calls"] == 0
    assert eng.metrics()["compiles"] == warm[T.COMPILE]["calls"] > 0

    def _fresh(x):
        return x * 3

    t = eng.clock.now()
    jax.jit(_fresh)(jnp.ones((3, 5)))
    grown = T.delta(eng.telemetry.snapshot(), after)[T.COMPILE]
    assert grown["calls"] >= 1 and grown["seconds"] > 0
    assert "jit(_fresh)" in eng.telemetry.labels(T.COMPILE, t, eng.clock.now())
