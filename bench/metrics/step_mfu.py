"""The roofline time of all model work completed in the window (each decode
step and each prefill, at the larger of its FLOP and byte bound) over the
window: the share of the chips' peak the served work used."""


def read(run):
    steps = run.decode_steps()
    rs = sum(run.roofline_s(run.decode_work([s])) for s in steps)
    rs += sum(run.roofline_s(run.prefill_work([r])) for r in run.prefilled())
    return 100.0 * rs / run.window_s
