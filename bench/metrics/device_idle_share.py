"""One minus the union of the device's op intervals over the traced
window, averaged over the chips used."""
from bench.trace import mean_busy_s


def read(run):
    if 0 not in run.devices_traced():
        return None
    return 100.0 * (1.0 - mean_busy_s(run.trace) / run.trace.window_s)
