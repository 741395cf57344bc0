"""Peak bytes in use on the fullest chip over the run (set-up included),
in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
