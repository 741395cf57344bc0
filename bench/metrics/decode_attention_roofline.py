"""The decode-attention kernel's roofline time in the traced decode steps
(K/V of the valid positions of the active slots, every layer) over its
device time there."""
from bench import counts
from bench.trace import op_seconds


def read(run):
    names = run.kernels.get("decode_attention")
    if 0 not in run.devices_traced() or not names:
        return None
    runs = run.traced_decode_runs()
    secs = op_seconds(run.trace, 0, names, within=runs)
    layers = run.cfg["num_hidden_layers"]

    def work(s, pos):
        w = counts.attention_decode_work(run.cfg, pos)
        return run.roofline_s(counts.Work(w.flops * layers, w.bytes * layers))

    per_step = run.traced_step_mean(work)
    if not secs or per_step is None:
        return None
    return 100.0 * len(runs) * per_step / secs
