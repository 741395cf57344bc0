"""Prefill FLOPs over prefill seconds (admission to first token) times the
peak bf16 rate."""


def read(run):
    reqs = run.prefilled()
    secs = sum(r.first_token_at - r.admitted_at for r in reqs)
    if not reqs or secs <= 0:
        return None
    return 100.0 * run.prefill_work(reqs).flops / (secs * run.peak_flops)
