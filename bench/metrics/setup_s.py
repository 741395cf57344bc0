"""Process start to window open: weights, compilation (or the compile
cache), warm-up and the fill the traffic needs."""


def read(run):
    return run.setup_s
