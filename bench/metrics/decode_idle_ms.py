"""Mean idle time of device 0 inside the engine's decode spans
(``serve.decode``: token grid, dispatch, sync on the picks, commit) that lie
wholly inside the traced window, on the profiler's clock."""
from bench import phases


def read(run):
    T = phases.names()
    if T is None or 0 not in run.devices_traced():
        return None
    idle = phases.span_idle_s(run.trace, T.DECODE)
    return 1000 * sum(idle) / len(idle) if idle else None
