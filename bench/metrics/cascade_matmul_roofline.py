"""The FP4 matmul kernel's roofline time in the traced decode steps (every
linear's logical M x K x N over the active rows) over its device time
there."""
from bench import counts
from bench.trace import op_seconds


def read(run):
    names = run.kernels.get("cascade_matmul")
    if 0 not in run.devices_traced() or not names:
        return None
    runs = run.traced_decode_runs()
    secs = op_seconds(run.trace, 0, names, within=runs)
    per_step = run.traced_step_mean(
        lambda s, pos: run.roofline_s(counts.cascade_matmul_work(run.cfg, s.produced, run=run)))
    if not secs or per_step is None:
        return None
    return 100.0 * len(runs) * per_step / secs
