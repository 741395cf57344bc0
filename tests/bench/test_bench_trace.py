"""The trace reduction, on a recorded TPU trace: one decode step of
codeqwen1.5-7b (8 slots, fused Pallas kernels) on a TPU v5e, cut from a
traced run of ``codeqwen7b.decode_saturated`` to the events of one run of
the decode program and the host spans inside it."""
import gzip
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data" / "codeqwen7b_decode_step.xplane.pb.gz"
LAYERS = 32


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "decode_step.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return TR.reduce_xplane(str(path))


def executions(red, kernel):
    return [(s, e) for n, s, e in red.devices[0].ops if n.rsplit(".", 1)[0] == kernel]


def test_instruction_name():
    assert TR.instruction_name(
        '%cascade_matmul.23 = bf16[8,4096]{1,0} custom-call(bf16[8,4096]{1,0} %fusion.40), '
        'custom_call_target="tpu_custom_call"') == "cascade_matmul.23"
    assert TR.instruction_name("ROOT %tuple.3 = (f32[]) tuple(f32[] %a)") == "tuple.3"


def test_one_decode_program_run(red):
    assert set(red.devices) == {0}
    runs = TR.module_runs(red, 0, "jit__decode_step")
    assert len(runs) == 1
    # without the benchmark's span the window is the extent of the ops
    assert red.window[0] <= runs[0][0] and runs[0][1] <= red.window[1]


def test_kernel_executions_per_step(red):
    # seven linears a layer and the head; one attention call a layer
    assert len(executions(red, "cascade_matmul")) == 7 * LAYERS + 1
    assert len(executions(red, "decode_attention")) == LAYERS
    names = {n for n, _, _ in red.devices[0].ops if n.startswith("cascade_matmul.")}
    run = TR.module_runs(red, 0, "jit__decode_step")
    inside = TR.op_seconds(red, 0, names, within=run)
    assert inside == TR.op_seconds(red, 0, names)
    assert 0 < inside < run[0][1] * 1e-9 - run[0][0] * 1e-9


def test_busy_and_idle(red):
    busy = TR.busy_s(red, 0)
    (s, e), = TR.module_runs(red, 0, "jit__decode_step")
    assert (e - s) * 1e-9 <= busy <= red.window_s
    gaps = TR.idle_gaps(red, 0)
    assert abs(sum(b - a for a, b in gaps) * 1e-9 + busy - red.window_s) < 1e-9
    assert TR.mean_busy_s(red) == busy


def test_no_collectives_on_one_chip(red):
    assert TR.collective_seconds(red, 0) == 0.0


def test_breakdown_leaves_out_control_flow(red):
    b = TR.breakdown(red)
    ops = b["device_ops"]
    assert 0 < len(ops) <= 10 and len(b["idle_gaps"]) <= 10
    assert not any(TR.CONTAINER.fullmatch(n) for n, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(isinstance(n, str) and t > 0 for n, t in b["idle_gaps"])
