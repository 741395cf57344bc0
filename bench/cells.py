"""Finding a cell's files by name.

``BENCHMARK.json`` (at the checkout root) names the cells and the metrics.
Everything that belongs to one cell, configuration, model type, traffic mix
or metric sits in a file of its own, found by the name alone:

* ``bench/workloads/<cell>.json``   - configuration, traffic, slot grid, limit
* ``bench/configs/<config>.json``   - the model as it is run
* ``bench/traffic/<traffic>.json``  - parameters of the one generator
* ``bench/metrics/<metric>.py``     - ``read(run) -> float | None``
* ``bench/families/<model_type>.py`` - the model as the program runs it, its
  plain reference and its counts, for every configuration whose file names
  that ``model_type`` (the interface: ``bench/families/__init__.py``)

A later cell, configuration, model type, traffic mix or metric is added
with new files and entries only: nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                      # "end_to_end" | "per_layer"
    workloads: Optional[tuple]     # None: every cell that reports ``moves``
    moves: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                   # bench/configs/<config>.json
    traffic: dict                  # bench/traffic/<traffic>.json
    workload: dict                 # bench/workloads/<cell>.json
    metrics: Dict[str, tuple]      # "end_to_end" / "per_layer" -> Metric, ...


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _metrics(bench: dict) -> List[Metric]:
    out = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench.get(kind, []):
            wl = m.get("workloads")
            out.append(Metric(m["name"], m["unit"], kind,
                              tuple(wl) if wl is not None else None, m.get("moves")))
    return out


def cell_metrics(bench: dict, cell: str) -> Dict[str, tuple]:
    """The end-to-end and per-layer metrics one cell reports.

    An end-to-end metric without ``workloads`` is in every cell; a per-layer
    metric without it is in every cell that reports the metric it moves."""
    ms = _metrics(bench)
    e2e = tuple(m for m in ms if m.kind == "end_to_end"
                and (m.workloads is None or cell in m.workloads))
    names = {m.name for m in e2e}
    per = tuple(m for m in ms if m.kind == "per_layer"
                and (cell in m.workloads if m.workloads is not None
                     else m.moves in names))
    return {"end_to_end": e2e, "per_layer": per}


def list_cells(root: Path = ROOT) -> List[str]:
    return [w["name"] for w in load_benchmark(root)["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {[w['name'] for w in bench['workloads']]})")
    bdir = root / "bench"
    workload = _load_json(bdir / "workloads" / f"{name}.json")
    if (workload["config"], workload["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: bench/workloads/{name}.json names "
                         f"{workload['config']}/{workload['traffic']}, "
                         f"BENCHMARK.json {entry['config']}/{entry['traffic']}")
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=_load_json(bdir / "configs" / f"{entry['config']}.json"),
                traffic=_load_json(bdir / "traffic" / f"{entry['traffic']}.json"),
                workload=workload, metrics=cell_metrics(bench, name))


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def family(model_type: str, root: Path = ROOT):
    """The module ``bench/families/<model_type>.py``, loaded once; a model
    type with no module is an error that names the file looked for."""
    path = root / "bench" / "families" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family module for model_type {model_type!r}: "
                                f"looked for {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device is
    an error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
