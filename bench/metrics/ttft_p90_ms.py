"""90th percentile of first token minus scheduled arrival, over every
request that arrived in the window."""
from bench.record import percentile


def read(run):
    v = percentile([r.first_token_at - r.created_at for r in run.arrivals()
                    if r.first_token_at], 90)
    return None if v is None else 1000 * v
