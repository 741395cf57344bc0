"""Prefill time (admission to first token) per thousand prompt tokens, over
the requests admitted and prefilled inside the window (extend + write)."""


def read(run):
    reqs = run.prefilled()
    tokens = sum(len(r.prompt) for r in reqs)
    if not tokens:
        return None
    return 1e6 * sum(r.first_token_at - r.admitted_at for r in reqs) / tokens
