"""The harness is driven by files: a new cell, configuration, traffic mix or
metric takes new files and entries only. And it never runs off the chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import cells as C

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def tree(tmp_path):
    """A copy of the benchmark's files (BENCHMARK.json and bench/)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_every_cell_loads():
    for name in C.list_cells():
        cell = C.load_cell(name)
        assert cell.metrics["end_to_end"] and cell.metrics["per_layer"]
        assert "setup_s" in {m.name for m in cell.metrics["end_to_end"]}
        for kind in ("end_to_end", "per_layer"):
            for m in cell.metrics[kind]:
                assert callable(C.metric_reader(m.name))


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    bench = C.load_benchmark()
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x.name for x in C.cell_metrics(bench, cell)["end_to_end"]}
            assert m["moves"] in e2e, (m["name"], cell)


def test_new_cell_and_metric_need_only_new_files(tree):
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "codeqwen7b.new_mix", "config": "codeqwen1.5-7b",
                               "traffic": "new_mix", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "scheduler",
                               "moves": "itl_p95_ms", "workloads": ["codeqwen7b.new_mix"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = json.loads((tree / "bench/traffic/decode_saturated.json").read_text())
    mix["output_lens"] = [[32, 64]]
    (tree / "bench/traffic/new_mix.json").write_text(json.dumps(mix))
    wl = json.loads((tree / "bench/workloads/codeqwen7b.decode_saturated.json").read_text())
    wl["traffic"] = "new_mix"
    (tree / "bench/workloads/codeqwen7b.new_mix.json").write_text(json.dumps(wl))
    (tree / "bench/metrics/new_metric.py").write_text("def read(run):\n    return 42.0\n")

    assert "codeqwen7b.new_mix" in C.list_cells(tree)
    cell = C.load_cell("codeqwen7b.new_mix", tree)
    assert cell.traffic["output_lens"] == [[32, 64]]
    assert [m.name for m in cell.metrics["per_layer"]] == ["new_metric"]
    assert C.metric_reader("new_metric", tree)(None) == 42.0


def test_unknown_device_kind_is_an_error():
    assert C.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        C.load_peaks("TPU v99")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "codeqwen7b.decode_saturated",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_in_a_tree_of_only_the_benchmark(tree):
    p = _run(tree)
    assert p.returncode != 0
    assert not p.stdout.strip()
