#!/usr/bin/env python3
"""The control of a cell's check, run on the chip (never by a benchmark run).

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 10

For each seed, in one process: serve a window of the cell exactly as
``bench/run.py`` does, then run the harness's own comparison twice on the
same sample of served requests. Once as a benchmark run does, judging the
tokens the program served; once with the control in the program's place:
at every served position, the token a float8 pass of the reference puts
first (``reference.lowp_picks``). One JSON line per seed, with each
verdict and its compared numbers. The control has to read ``correct``
false; the check's limit lies between the program's largest reading and
the control's smallest.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import cells as C  # noqa: E402
from bench import check, reference  # noqa: E402
from bench import run as R  # noqa: E402


def readings(cell, seed: int, seconds: float, devices, peaks, size=None) -> dict:
    rec, params = R.serve(cell, seed, seconds, False, devices, peaks,
                          time.monotonic(), size)
    gc.collect()
    reqs = check.served(rec)
    _, failed = check.attempted_failed(rec)
    out = {"seed": seed}
    for name, pick in (("program", None), ("control", reference.lowp_picks)):
        compared = check.compare(params, rec.cfg, reqs, cell.workload, seed, pick)
        out[name] = {"correct": check.verdict(compared, failed), "compared": compared}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devices = R.require_chips(cell.chips)
    peaks = C.load_peaks(devices[0].device_kind)
    R.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds, devices, peaks)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
