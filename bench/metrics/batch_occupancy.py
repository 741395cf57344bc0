"""Active slots per decode step over ``max_batch``, over the window's decode
steps (scheduler)."""


def read(run):
    steps = run.decode_steps()
    if not steps:
        return None
    slots = len(steps) * run.cell.workload["max_batch"]
    return 100.0 * sum(s.produced for s in steps) / slots
