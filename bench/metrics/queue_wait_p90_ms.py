"""90th percentile of admission minus scheduled arrival, over the requests
that arrived in the window (scheduler)."""
from bench.record import percentile


def read(run):
    v = percentile([r.admitted_at - r.created_at for r in run.arrivals()
                    if r.admitted_at], 90)
    return None if v is None else 1000 * v
