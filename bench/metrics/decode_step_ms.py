"""Mean host time of the decode half of an engine step (it ends in a host
sync), over the window's decode steps."""


def read(run):
    steps = run.decode_steps()
    return 1000 * sum(s.decode_s for s in steps) / len(steps) if steps else None
