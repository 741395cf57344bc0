#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` and the files under ``bench/``. The run draws its
weights and requests from ``--seed``, warms up, measures for ``--seconds``,
then checks what the window served against the plain reference. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer ones with ``--trace 1``), ``device``, ``breakdown`` (traced
runs) and, last, ``compared``: each number the check compared, with its
limit.

Exits non-zero, printing no result, unless JAX finds as many TPU chips as
the cell asks for.
"""
from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cells as C  # noqa: E402
from bench import check, serving  # noqa: E402
from bench import trace as TR  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench.record import Record  # noqa: E402

#: where a traced run writes its profile (removed once it is read)
TRACE_DIR = ROOT / ".bench_out" / "trace"
#: seconds of the window's end that a traced run profiles
TRACE_SECONDS = 3.0


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        fail(f"the cell needs {chips} TPU chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, always at the fixed
    ``<checkout>/.jax_cache``. Not ``repro.launch.compile_cache``, which
    follows ``JAX_COMPILATION_CACHE_DIR`` where it is set: the benchmark
    keeps its cache inside its own checkout, so that two checkouts never
    share one, and it caches every program, however short its compile, so
    that a second run compiles nothing."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def kernel_instructions(engine) -> dict:
    """Pallas kernel -> names of its ``tpu_custom_call`` instructions in the
    compiled decode step, e.g. ``{"cascade_matmul": {"cascade_matmul.3"}}``.
    The kernel is the jitted wrapper its ``op_name`` names
    (``.../jit(cascade_matmul)/pallas_call``), else the instruction's own
    name less its number."""
    import re
    out: dict = {}
    for line in engine.lower_step("decode").as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = TR.instruction_name(line)
        kernel = re.search(r'op_name="[^"]*?jit\((\w+)\)/pallas_call', line)
        out.setdefault(kernel.group(1) if kernel else name.rsplit(".", 1)[0],
                       set()).add(name)
    return out


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def serve(cell, seed: int, seconds: float, trace: bool, devices, peaks: dict,
          start: float, size=None):
    """Build the cell's system from ``seed``, warm it up and run the window.
    Returns ``(record, params)``; the engine, and its cache, end here.
    ``size`` lays a test's smaller shapes over the configuration."""
    import jax
    cfg = serving.model_config(cell, size)
    w = cell.workload
    engine, params = serving.build_engine(cell, cfg, seed, devices)
    planned = T.generate(cell.traffic, seed, seconds, w["max_batch"],
                         cfg["vocab_size"])
    if cell.traffic["arrival"] == "poisson":
        serving.warm_up(engine, cfg["vocab_size"], seed,
                        max(len(p.prompt) for p in planned))
    trace_fn = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python tracer: it would time every Python call of the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        spans = []

        def trace_fn(on: bool):
            if on:
                jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
                # made once the profiler runs, or it records nothing
                spans.append(jax.profiler.TraceAnnotation(TR.WINDOW_SPAN))
                spans[0].__enter__()
            else:
                spans[0].__exit__(None, None, None)
                jax.profiler.stop_trace()

    drive = serving.drive(engine, planned, cell.traffic, seconds, trace_fn,
                          min(TRACE_SECONDS, seconds))
    rec = Record(cell=cell, cfg=cfg, drive=drive, setup_s=drive.window[0] - start,
                 peaks=peaks, chips=len(devices),
                 memory_peak_bytes=memory_peak(devices))
    if trace:
        rec.trace = TR.reduce_xplane(TR.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        rec.kernels = kernel_instructions(engine) if engine.fused else {}
    return rec, params


def execute(cell, seed: int, seconds: float, trace: bool, devices, peaks: dict,
            start: float, size=None, pick=None) -> dict:
    """One run of ``cell``; returns the result object. ``pick`` puts the
    control's tokens in the served ones' place (``check.compare``)."""
    rec, params = serve(cell, seed, seconds, trace, devices, peaks, start, size)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = C.metric_reader(m.name)(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    attempted, failed = check.attempted_failed(rec)
    late = rec.drive.lateness
    if late:
        print(f"generator lateness: median {float(sorted(late)[len(late) // 2])!r} s, "
              f"max {float(max(late))!r} s over {len(late)} arrivals", file=sys.stderr)
    gc.collect()        # the program's state is freed before the reference runs
    compared = check.compare(params, rec.cfg, check.served(rec), cell.workload,
                             seed, pick)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": rec.memory_peak_bytes}
    result = {"correct": check.verdict(compared, failed),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if rec.devices_traced():
        device["busy_s"] = TR.mean_busy_s(rec.trace)
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = TR.breakdown(rec.trace)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devices = require_chips(cell.chips)
    try:
        peaks = C.load_peaks(devices[0].device_kind)
    except KeyError as e:
        fail(str(e))
    enable_compile_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                     peaks, START)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
