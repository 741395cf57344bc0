"""Everything that depends on the model is found by the configuration's
``model_type``: a new kind of model takes new files only, an unknown kind
is an error that names the file looked for, and a weight leaf that no rule
covers still raises."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells as C
from bench import counts, reference, serving, weights

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def tree(tmp_path):
    """A copy of the benchmark's files (BENCHMARK.json and bench/)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


#: a family of its own that runs the Qwen2 block, and records which of its
#: parts the harness called
TWIN = '''
from bench.families import qwen2
from bench.families.qwen2 import (REFERENCE_KEYS, attention_decode_work,
                                  kv_bytes_per_token, layer_linears, linears)

USED = set()


def model(name, cfg):
    USED.add("model")
    return qwen2.model(name, cfg)


def forward(params, tokens, cfg, lowp=False):
    USED.add("forward")
    return qwen2.forward(params, tokens, cfg, lowp)


def decode_step_work(cfg, active, positions, run=None):
    USED.add("decode_step_work" + ("(run)" if run is not None else ""))
    return qwen2.decode_step_work(cfg, active, positions)


def prefill_work(cfg, prompt, chunk, run=None):
    return qwen2.prefill_work(cfg, prompt, chunk)


def cascade_matmul_work(cfg, active, run=None):
    return qwen2.cascade_matmul_work(cfg, active)
'''

TWIN_RUN = """
import json, sys, time
import jax
from bench import cells as C, check, run as R
seed = 2 ** 31 + 51
cell = C.load_cell("twin7b.decode_saturated")
peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
rec, params = R.serve(cell, seed, 4.0, False, jax.devices()[:1], peaks,
                      time.monotonic(), size=cell.config["test_size"])
mfu = C.metric_reader("step_mfu")(rec)
compared = check.compare(params, rec.cfg, check.served(rec), cell.workload, seed)
fam = C.family("qwen2_twin")
print(json.dumps({"correct": check.verdict(compared, 0), "compared": compared,
                  "step_mfu": mfu, "used": sorted(fam.USED), "file": fam.__file__}))
"""


def test_new_model_type_needs_only_new_files(tree):
    """A configuration of a new ``model_type`` with its family module, a
    workload file and ``BENCHMARK.json`` entries: it loads, builds its
    engine, draws its weights, serves a window and has its served tokens
    judged by its own reference, at its test size on the CPU."""
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "twin-7b", "source": "a test",
                             "file": "bench/configs/twin-7b.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "twin7b.decode_saturated", "config": "twin-7b",
                               "traffic": "decode_saturated", "chips": 1, "why": "a test"})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    config = json.loads((tree / "bench/configs/codeqwen1.5-7b.json").read_text())
    config["model_type"] = "qwen2_twin"
    (tree / "bench/configs/twin-7b.json").write_text(json.dumps(config))
    wl = json.loads((tree / "bench/workloads/codeqwen7b.decode_saturated.json").read_text())
    wl["config"] = "twin-7b"
    wl["check"]["max_logit_gap"] = 0.09     # the test size's limit (test_bench_check)
    (tree / "bench/workloads/twin7b.decode_saturated.json").write_text(json.dumps(wl))
    (tree / "bench/families/qwen2_twin.py").write_text(TWIN)
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            copy = tree / path.relative_to(ROOT)
            assert copy.read_bytes() == path.read_bytes(), copy

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tree), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", TWIN_RUN], cwd=tree, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["compared"]
    assert Path(res["file"]) == tree / "bench/families/qwen2_twin.py"
    assert res["used"] == ["decode_step_work(run)", "forward", "model"]
    assert res["step_mfu"] > 0


def test_unknown_model_type_names_the_file_it_looked_for(tree, monkeypatch):
    """No fallback: the family lookup, the engine (before any weight is
    drawn), the reference and the counts all fail on a model type with no
    module, naming the file looked for."""
    looked_for = tree / "bench/families/no_such_model.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(looked_for))):
        C.family("no_such_model", tree)

    def drawn(*a, **k):
        raise AssertionError("weights were drawn")

    monkeypatch.setattr(serving, "make_params", drawn)
    cell = C.load_cell("codeqwen7b.decode_saturated")
    cfg = serving.model_config(cell, dict(cell.config["test_size"],
                                          model_type="no_such_model"))
    missing = re.escape(str(ROOT / "bench/families/no_such_model.py"))
    with pytest.raises(FileNotFoundError, match=missing):
        serving.build_engine(cell, cfg, 1, jax.devices()[:1])
    with pytest.raises(FileNotFoundError, match=missing):
        reference.served_gaps({}, cfg, [1, 2], [3], 8)
    with pytest.raises(FileNotFoundError, match=missing):
        counts.decode_step_work(cfg, active=1, positions=1)


class _RouterModel:
    """A tree with a leaf the generic rules do not cover (a router's f32
    weight) beside one they do (a norm's scale)."""

    def init_params(self, key, ccfg):
        return {"router": jnp.zeros((8, 4), jnp.float32),
                "ln": {"scale": jnp.ones((8,), jnp.float32)}}


def _router_rule(names, sds, key, k_in):
    if names[-1] == "router":
        return 0.02 * jax.random.normal(key, sds.shape, sds.dtype)
    return None


@pytest.mark.parametrize("rule", [None, lambda names, sds, key, k_in: None],
                         ids=["no_rule", "rule_declines"])
def test_a_leaf_no_rule_covers_raises(rule):
    with pytest.raises(ValueError, match="router"):
        weights.make_params(_RouterModel(), None, 7, fill=rule)


def test_a_family_rule_draws_the_leaf_it_covers():
    p = weights.make_params(_RouterModel(), None, 7, fill=_router_rule)
    assert p["router"].shape == (8, 4) and float(jnp.std(p["router"])) > 0
    scale = np.asarray(p["ln"]["scale"])
    assert np.all(scale != 1.0) and np.abs(scale - 1.0).max() < 1.0
