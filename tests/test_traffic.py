"""Open-loop traffic generator + clock protocol: determinism, distributions.

The generator is the root of the traffic harness's reproducibility claim:
same seed => identical trace (arrival times, prompts, lengths, SLO
stamps). Property tests pin that, plus the statistical contracts — Poisson
mean inter-arrival within tolerance of 1/rate, mixture lengths inside
their configured bounds — and the VirtualClock's monotonicity.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.traffic import (Clock, MonotonicClock, TrafficConfig,
                                 VirtualClock, poisson_trace)

_seeds = st.integers(0, 2**31 - 1)


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------

def test_clock_protocol():
    assert isinstance(MonotonicClock(), Clock)
    assert isinstance(VirtualClock(), Clock)


def test_virtual_clock_advances_and_never_rewinds():
    clk = VirtualClock(start=5.0)
    assert clk.now() == 5.0
    assert clk.advance(1.5) == 6.5
    assert clk.advance_to(6.0) == 6.5      # past target: no-op
    assert clk.advance_to(10.0) == 10.0
    with pytest.raises(AssertionError):
        clk.advance(-0.1)


def test_monotonic_clock_is_monotone():
    clk = MonotonicClock()
    a = clk.now()
    assert clk.now() >= a


# ---------------------------------------------------------------------------
# generator: determinism + shape
# ---------------------------------------------------------------------------

def _sig(trace):
    return [(a.at_s, a.request.uid, a.request.prompt.tolist(),
             a.request.max_new_tokens, a.request.slo_ttft_s,
             a.request.deadline_s) for a in trace]


@settings(max_examples=25, deadline=None)
@given(_seeds)
def test_same_seed_identical_trace(seed):
    cfg = TrafficConfig(rate_rps=12.0, n_requests=20, seed=seed,
                        prompt_lens=((2, 8), (16, 24)), prompt_mix=(3.0, 1.0),
                        output_lens=((1, 4),), slo_ttft_s=0.3, deadline_s=1.0)
    assert _sig(poisson_trace(cfg)) == _sig(poisson_trace(cfg))


def test_different_seed_different_trace():
    cfg = TrafficConfig(n_requests=16, seed=0)
    assert (_sig(poisson_trace(cfg))
            != _sig(poisson_trace(TrafficConfig(n_requests=16, seed=1))))


def test_trace_is_open_loop_shaped():
    """Arrivals sorted, unstamped (the driver re-bases onto its clock),
    SLO fields threaded through to every request."""
    cfg = TrafficConfig(rate_rps=5.0, n_requests=12, slo_ttft_s=0.25,
                        deadline_s=2.0, seed=3)
    trace = poisson_trace(cfg)
    assert len(trace) == 12
    ats = [a.at_s for a in trace]
    assert ats == sorted(ats) and ats[0] > 0.0
    for a in trace:
        assert a.request.created_at == 0.0
        assert a.request.slo_ttft_s == 0.25
        assert a.request.deadline_s == 2.0


@settings(max_examples=20, deadline=None)
@given(_seeds, st.floats(1.0, 50.0))
def test_poisson_mean_interarrival_within_tolerance(seed, rate):
    """Mean gap of n exponential(1/rate) draws concentrates at 1/rate:
    the sample mean is within 5 sigma = 5/(rate*sqrt(n)) of it."""
    n = 512
    cfg = TrafficConfig(rate_rps=rate, n_requests=n, seed=seed)
    ats = np.asarray([a.at_s for a in poisson_trace(cfg)])
    gaps = np.diff(np.concatenate([[0.0], ats]))
    assert abs(gaps.mean() - 1.0 / rate) < 5.0 / (rate * np.sqrt(n))


@settings(max_examples=20, deadline=None)
@given(_seeds,
       st.integers(1, 12), st.integers(0, 12),
       st.integers(1, 12), st.integers(0, 12))
def test_lengths_respect_configured_bounds(seed, plo, pspan, olo, ospan):
    """Every prompt/output length lands inside SOME configured component
    range — the mixture never leaks outside its support."""
    phi, ohi = plo + pspan, olo + ospan
    cfg = TrafficConfig(n_requests=64, seed=seed,
                        prompt_lens=((plo, phi), (plo + 20, phi + 20)),
                        prompt_mix=(1.0, 2.0),
                        output_lens=((olo, ohi),))
    for a in poisson_trace(cfg):
        n = len(a.request.prompt)
        assert (plo <= n <= phi) or (plo + 20 <= n <= phi + 20)
        assert olo <= a.request.max_new_tokens <= ohi


def test_bad_mixture_rejected():
    with pytest.raises(AssertionError):
        poisson_trace(TrafficConfig(prompt_lens=((8, 4),)))      # hi < lo
    with pytest.raises(AssertionError):
        poisson_trace(TrafficConfig(prompt_lens=((4, 8), (2, 3)),
                                    prompt_mix=(1.0,)))          # arity
    with pytest.raises(AssertionError):
        poisson_trace(TrafficConfig(prompt_mix=(0.0,)))          # zero mass
