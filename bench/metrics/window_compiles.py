"""Compilations in the process inside the window, of jitted functions and
eager ops alike (``jax.compile``); where there are any, standard error
names what was compiled."""
import sys

from bench import phases


def read(run):
    w = phases.window_counters(run)
    if w is None:
        return None
    T = phases.names()
    n = w.get(T.COMPILE, {}).get("calls", 0)
    if n:
        print(f"window_compiles: {n} inside the window: "
              f"{T.last().labels(T.COMPILE, *run.drive.window)}", file=sys.stderr)
    return float(n)
