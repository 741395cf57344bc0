"""The check that decides ``correct``: its control, put in the program's
place, comes out not correct at the cell's own limit where the program
passes, and a run whose timed path is broken underneath comes out not
correct. Everything but the harness's look for a chip runs, on the cells'
own serving path (fused kernels, in interpret mode off the chip) at a size
the CPU holds: each fault the timed path can have (a token altered where it
is produced, a step that hands its state back unchanged, half of the slot
grid left out, the exchange between chips left out) reads not correct."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import cells as C
from bench import check, control, reference, serving
from bench import run as R
from repro.serve.engine import ServeEngine

ROOT = Path(__file__).resolve().parents[2]

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELLS = C.list_cells()
#: an open-loop mix, as a later cell's traffic file would give it
POISSON = {"arrival": "poisson", "rate_rps": 1.0, "lead_s": 2.0,
           "prompt_lens": [[32, 96], [97, 200]], "prompt_mix": [0.7, 0.3],
           "output_lens": [[4, 24]], "output_mix": [1.0]}


def run_cell(cell, seed, seconds=6.0, pick=None):
    if isinstance(cell, str):
        cell = C.load_cell(cell)
    return R.execute(cell, seed, seconds, False, jax.devices()[:1], PEAKS,
                     time.monotonic(), size=cell.config["test_size"], pick=pick)


#: the check's limit at the test size, set between that size's readings on
#: the CPU over six seeds a cell: the program at most 0.043, the control at
#: least 0.143 (the cells' own limits hold at their own size, on the chip)
TEST_LIMIT = 0.09


def at_test_limit(cell):
    return dataclasses.replace(cell, workload=dict(
        cell.workload, check=dict(cell.workload["check"], max_logit_gap=TEST_LIMIT)))


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_not_correct(name):
    """The float8 reference's picks, judged by the harness's own comparison,
    read ``correct`` false; the served tokens of the same run read it true."""
    cell = at_test_limit(C.load_cell(name))
    r = control.readings(cell, 2 ** 31 + 21, 4.0, jax.devices()[:1], PEAKS,
                         size=cell.config["test_size"])
    assert r["program"]["correct"], r["program"]
    assert not r["control"]["correct"], r["control"]
    res = run_cell(cell, 22, pick=reference.lowp_picks)
    assert not res["correct"], res["compared"]
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] == TEST_LIMIT


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_cell(name, 31)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {m.name for m in C.load_cell(name).metrics["end_to_end"]}


def test_open_loop_run_is_correct():
    """A Poisson mix drives the open-loop path: arrivals due in the window
    all get their first token, the latency readers find them, and what was
    served passes the check."""
    cell = C.load_cell("codeqwen7b.decode_saturated")
    cell = dataclasses.replace(cell, traffic=POISSON,
                               workload=dict(cell.workload, max_batch=4))
    rec, params = R.serve(cell, 33, 6.0, False, jax.devices()[:1], PEAKS,
                          time.monotonic(), size=cell.config["test_size"])
    assert len(rec.arrivals()) >= 4
    assert all(r.first_token_at for r in rec.arrivals())
    for name in ("ttft_p90_ms", "queue_wait_p90_ms"):
        assert C.metric_reader(name)(rec) > 0
    compared = check.compare(params, rec.cfg, check.served(rec), cell.workload, 33)
    assert check.verdict(compared, 0), compared


def _break_decode(fault):
    """``build_engine`` whose decode step has ``fault`` planted in it."""
    build = serving.build_engine

    def broken(*a, **k):
        engine, params = build(*a, **k)
        fn, half = engine._decode_fn, engine.scfg.max_batch // 2
        if fault == "state_unchanged":      # the step hands its cache back as it came
            engine._decode_fn = lambda p, t, c: (
                fn(p, t, jax.tree.map(jnp.copy, c))[0], c)
        elif fault == "half_batch":         # the second half of the slots is left out
            engine._decode_fn = lambda p, t, c: fn(p, t.at[half:].set(0), c)
        return engine, params
    return broken


def _alter_tokens(monkeypatch):
    commit = ServeEngine._commit_token

    def altered(self, req, tok):
        if len(req.tokens_out) == 3:           # the fourth token of every request
            tok = (tok + 1) % self.model.cfg.vocab
        commit(self, req, tok)

    monkeypatch.setattr(ServeEngine, "_commit_token", altered)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged", "half_batch"])
def test_broken_decode_is_not_correct(monkeypatch, fault):
    if fault == "token_altered":
        _alter_tokens(monkeypatch)
    else:
        monkeypatch.setattr(serving, "build_engine", _break_decode(fault))
    res = run_cell("codeqwen7b.decode_saturated", 32)
    assert not res["correct"]
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


#: a four-chip cell of the 32B configuration; its cell is not yet in
#: ``BENCHMARK.json``, so the test adds it to a copy of the benchmark's files
MESH_CELL = {"name": "qwen32b-tp4.decode_saturated", "config": "qwen2.5-32b-tp4",
             "traffic": "decode_saturated", "chips": 4, "why": "the mesh path"}
MESH_WORKLOAD = {"config": "qwen2.5-32b-tp4", "traffic": "decode_saturated",
                 "max_batch": 16, "max_len": 1024,
                 "check": {"requests": 4, "max_logit_gap": 1.0}}

MESH_RUN = """
import json, sys, time
import jax
from bench import cells as C, run as R
import repro.distributed.sharding as shd
cell = C.load_cell("qwen32b-tp4.decode_saturated")
peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
if sys.argv[1] == "exchange_left_out":
    # each chip contracts over its own quarter of the features, as if the
    # all-gather of the activations before every linear never came
    keep = shd.constrain_matmul_input
    def local(x):
        x = keep(x)
        quarter = x.shape[-1] // cell.config["mesh"]["model"]
        return x.at[..., quarter:].set(0)
    shd.constrain_matmul_input = local
res = R.execute(cell, 41, 6.0, False, jax.devices()[:4], peaks, time.monotonic(),
                size=cell.config["test_size"])
print(json.dumps({"correct": res["correct"], "compared": res["compared"]}))
"""


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_mesh_cell_without_its_exchange_is_not_correct(fault, tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(MESH_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/workloads/qwen32b-tp4.decode_saturated.json").write_text(
        json.dumps(MESH_WORKLOAD))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tmp_path), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", MESH_RUN, fault], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] == (fault == "none"), res["compared"]
