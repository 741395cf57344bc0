"""The engine's admission span (``serve.admit``: prefill, first-token pick
and slot write of the requests it admits) over the whole window, as a share
of the window (scheduler)."""
from bench import phases


def read(run):
    w = phases.window_counters(run)
    if w is None:
        return None
    return 100.0 * w.get(phases.names().ADMIT, {}).get("seconds", 0.0) / run.window_s
