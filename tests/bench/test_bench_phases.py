"""The readers of the engine's own spans and counters: the device's idle
time inside a host span, the engine's counters over a run's window, and the
three metrics that read them, on hand-built records."""
import sys

import pytest

import repro.serve
from bench import cells as C
from bench import phases
from bench import trace as TR
from bench.record import Record
from bench.serving import Drive, Step
from repro.serve import telemetry as T
from repro.serve.traffic import VirtualClock


def reduced(host):
    """A window of 100-1000 ns; device 0 busy at 250-350, 480-520 and
    600-650 (idle 100-250, 350-480, 520-600 and 650-1000)."""
    ops = [("a.1", 250, 350), ("b.1", 480, 520), ("a.1", 600, 650)]
    return TR.Reduced((100, 1000), {0: TR.DeviceOps(ops, [])}, host)


def test_idle_inside_each_span_is_clipped_to_it():
    red = reduced([(T.DECODE, 200, 400), (T.DECODE, 500, 700),
                   (T.COMMIT, 300, 360), (TR.WINDOW_SPAN, 100, 1000)])
    assert phases.span_idle_s(red, T.DECODE) == pytest.approx([100e-9, 130e-9])
    assert phases.span_idle_s(red, T.COMMIT) == pytest.approx([10e-9])
    assert phases.span_idle_s(red, T.DRAFT) == []


def test_a_span_across_the_windows_edge_is_left_out():
    red = reduced([(T.DECODE, 50, 150), (T.DECODE, 200, 400),
                   (T.DECODE, 900, 1100)])
    assert phases.span_idle_s(red, T.DECODE) == pytest.approx([100e-9])


def record(drive, trace=None):
    return Record(cell=C.load_cell("codeqwen7b.decode_saturated"), cfg={},
                  drive=drive, setup_s=1.0, peaks={}, chips=1,
                  memory_peak_bytes=0, trace=trace)


def served(steps=4, admit_s=0.25, compile_at=None):
    """A telemetry whose engine ran ``steps`` steps of 1 s from t=10, each
    spending ``admit_s`` in admission, and the drive of those steps."""
    clk = VirtualClock(start=10.0)
    tel = T.Telemetry(clk)
    drive = Drive([], [])
    for i in range(steps):
        start = clk.now()
        with tel.step():
            with tel.span(T.ADMIT):
                clk.advance(admit_s)
            with tel.span(T.DECODE):
                clk.advance(1.0 - admit_s)
        if i == compile_at:
            T._on_event("/jax/core/compile/jaxpr_to_mlir_module_duration",
                        0.5, fun_name="jit(_extend_step)")
        drive.steps.append(Step(start, clk.now(), 16, 1.0 - admit_s))
    drive.window = (11.0, clk.now())       # the first step fills the slots
    return record(drive)


def reader(name):
    return C.metric_reader(name)


def test_admission_share_reads_the_window():
    assert reader("admission_share")(served()) == pytest.approx(25.0)


def test_window_compiles_counts_and_names_what_compiled(capsys):
    assert reader("window_compiles")(served()) == 0.0
    assert reader("window_compiles")(served(compile_at=0)) == 0.0  # set-up
    assert reader("window_compiles")(served(compile_at=2)) == 1.0
    assert "jit(_extend_step)" in capsys.readouterr().err


def test_counters_of_another_engine_read_nothing():
    run = served()
    T.Telemetry(VirtualClock(start=10.0))           # a later engine's
    assert reader("admission_share")(run) is None
    assert reader("window_compiles")(run) is None


def test_decode_idle_ms_reads_the_traced_decode_spans():
    run = served()
    run.trace = reduced([(T.DECODE, 200, 400), (T.DECODE, 500, 700)])
    assert reader("decode_idle_ms")(run) == pytest.approx(115e-6)
    run.trace = reduced([])
    assert reader("decode_idle_ms")(run) is None


def test_a_program_without_telemetry_reads_nothing(monkeypatch):
    run = served()
    run.trace = reduced([(T.DECODE, 200, 400)])
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    monkeypatch.delattr(repro.serve, "telemetry")
    for name in ("admission_share", "decode_idle_ms", "window_compiles"):
        assert reader(name)(run) is None
