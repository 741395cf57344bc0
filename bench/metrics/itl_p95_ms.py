"""95th percentile of the gaps between consecutive committed tokens of one
request, both inside the window."""
from bench.record import percentile


def read(run):
    v = percentile(run.inter_token_gaps(), 95)
    return None if v is None else 1000 * v
