"""Device 0's time in collectives (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all) over the traced window."""
from bench.trace import collective_seconds


def read(run):
    if 0 not in run.devices_traced() or run.chips < 2:
        return None
    return 100.0 * collective_seconds(run.trace, 0) / run.trace.window_s
