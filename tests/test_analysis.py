"""Tests for the roofline analysis pipeline: HLO trip-count correction,
analytic FLOPs/params model vs real param trees, shape applicability, and a
subprocess end-to-end dry-run cell."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hlo_analysis_corrects_scan_trip_count():
    """cost_analysis counts a while body once; the structural analyzer must
    recover trip_count x body dot FLOPs exactly."""
    sys.path.insert(0, REPO)
    from benchmarks import hlo_analysis

    L, M, K = 12, 32, 64

    def f(x, ws):
        def step(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(step, x, ws)
        return out.sum()

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                            jax.ShapeDtypeStruct((L, K, K), jnp.float32)).compile()
    r = hlo_analysis.analyze(comp.as_text())
    expected = L * 2 * M * K * K
    assert r["dot_flops"] == expected, (r["dot_flops"], expected)
    # and the uncorrected number is exactly one iteration
    assert r["dot_flops_uncorrected"] == expected / L


def test_param_count_matches_real_init():
    """Analytic param_count (used for MODEL_FLOPS) vs the actual full-config
    param tree, via eval_shape (no allocation)."""
    from repro.core import flops as F
    from repro.core.cascade import CascadeConfig
    from repro.models import registry

    ccfg = CascadeConfig(mode="train")
    for arch in ["qwen2.5-32b", "phi4-mini-3.8b", "olmoe-1b-7b", "mamba2-370m",
                 "deepseek-v2-236b", "recurrentgemma-2b", "musicgen-large"]:
        cfg, model = registry.load(arch)
        shapes = jax.eval_shape(lambda m=model: m.init_params(jax.random.PRNGKey(0), ccfg))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        analytic = F.param_count(cfg)["total"]
        rel = abs(actual - analytic) / actual
        assert rel < 0.015, f"{arch}: analytic {analytic:.3e} vs actual {actual:.3e} ({rel:.3%})"


def test_known_param_totals():
    """Sanity anchors: the archs' nominal sizes."""
    from repro.core import flops as F
    from repro.models import registry
    expect = {"qwen2.5-32b": (30e9, 36e9), "phi4-mini-3.8b": (3.3e9, 4.4e9),
              "deepseek-v2-236b": (220e9, 250e9), "olmoe-1b-7b": (6.0e9, 7.5e9),
              "mamba2-370m": (0.3e9, 0.45e9), "nemotron-4-15b": (14e9, 17e9)}
    for arch, (lo, hi) in expect.items():
        total = F.param_count(registry.get_config(arch))["total"]
        assert lo < total < hi, f"{arch}: {total:.3e} outside [{lo:.1e},{hi:.1e}]"
    ds = F.param_count(registry.get_config("deepseek-v2-236b"))
    assert 18e9 < ds["active"] < 25e9  # DeepSeek-V2: ~21B active


def test_shape_applicability_covers_40_cells():
    from repro.configs import base as cfgbase
    from repro.models import registry
    cells = [(a, s) for a in registry.ALIASES for s in cfgbase.SHAPES]
    assert len(cells) == 40
    runnable = [c for c in cells if cfgbase.shape_applicable(
        registry.get_config(c[0]), cfgbase.SHAPES[c[1]])]
    skipped = [c for c in cells if c not in runnable]
    assert len(runnable) == 32 and len(skipped) == 8  # 8 full-attn archs skip long_500k
    # exactly the sub-quadratic archs keep long_500k
    keep = {a for (a, s) in runnable if s == "long_500k"}
    assert keep == {"mamba2-370m", "recurrentgemma-2b"}


def test_input_specs_all_cells_no_allocation():
    from repro.configs import base as cfgbase
    from repro.models import registry
    for a in registry.ALIASES:
        cfg = registry.get_config(a)
        for s in cfgbase.SHAPES.values():
            specs = cfgbase.input_specs(cfg, s)
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)
            if cfg.input_embeds:
                assert "inputs_embeds" in specs and "tokens" not in specs


@pytest.mark.slow
def test_dryrun_cell_subprocess_single_and_megatron():
    """End-to-end: a real dry-run cell on a 256-virtual-device mesh in a
    fresh interpreter (XLA_FLAGS must be set before jax init), both TP
    policies."""
    for policy in ["cascade", "megatron"]:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "qwen2-vl-2b", "--shape", "decode_32k",
             "--tp-policy", policy],
            cwd=REPO, env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
            capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-800:]
        rec = json.loads([l for l in proc.stdout.splitlines() if l.startswith("{")][0])
        assert rec["status"] == "ok" and rec["tp_policy"] == policy
        assert rec["memory"]["peak_bytes"] < 16e9


@pytest.mark.slow
def test_train_and_serve_cli_subprocess():
    """The launchers run end-to-end from their CLIs (the deployment path)."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    t = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "phi4-mini-3.8b",
         "--smoke", "--steps", "6", "--batch", "2", "--seq", "32", "--qat"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert t.returncode == 0 and "final loss" in t.stdout, t.stdout[-400:] + t.stderr[-400:]
    s = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "codeqwen1.5-7b",
         "--smoke", "--requests", "3", "--max-batch", "2", "--max-new", "4"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert s.returncode == 0 and "served 3 requests" in s.stdout, s.stdout[-400:] + s.stderr[-400:]


# ---------------------------------------------------------------------------
# report.py loaders: schema tolerance
# ---------------------------------------------------------------------------

def test_report_load_tolerates_missing_keys(tmp_path):
    """Rows from older sweeps may lack arch/shape; load() must key them
    under '?' instead of KeyError-ing the whole report away."""
    sys.path.insert(0, REPO)
    from benchmarks import report

    p = tmp_path / "roof.json"
    p.write_text(json.dumps([
        {"arch": "a1", "shape": "s1", "status": "ok"},
        {"status": "ok", "decode_bound_tokens_per_s": 5.0},   # legacy row
    ]))
    d = report.load(str(p))
    assert ("a1", "s1") in d and ("?", "?") in d
    assert report.load(str(tmp_path / "missing.json")) == {}


def test_report_fused_table_tolerates_missing_bound_fields():
    """A fused row without weight_stream_bytes_per_device renders with a
    0.00 GB cell — the table never drops because one field is absent."""
    sys.path.insert(0, REPO)
    from benchmarks import report

    rows = [
        {"mode": "fused", "family": "transformer", "max_batch": 2,
         "tokens_per_s": 100.0, "decode_bound_tokens_per_s": 1000.0,
         "fraction_of_bound": 0.1},                # no weight_stream bytes
        {"mode": "fp4", "family": "transformer", "max_batch": 2,
         "tokens_per_s": 80.0},
    ]
    lines = report.fused_lines(rows)
    row = [l for l in lines if l.startswith("| transformer")]
    assert len(row) == 1
    assert "0.00" in row[0] and "100.0" in row[0] and "80.0" in row[0]
    assert report.fused_lines([{"mode": "batched"}]) == []


def test_report_load_tolerates_prefix_row_keys(tmp_path):
    """The paged/prefix rows add keys (prefix_hit_rate, ttft_warm_p50_s,
    prefix_probe shape) no earlier loader knew about; load() must keep
    keying them by (arch, shape) without complaint."""
    sys.path.insert(0, REPO)
    from benchmarks import report

    p = tmp_path / "serving.json"
    p.write_text(json.dumps([
        {"arch": "a1", "shape": "prefix_probe", "mode": "paged",
         "status": "ok", "prefix_hit_rate": 0.5, "ttft_cold_s": 0.06,
         "ttft_warm_s": 0.02},
        {"arch": "a1", "shape": "serve_decode_b2", "mode": "paged",
         "status": "ok", "pages_in_use": 12, "page_size": 16},
    ]))
    d = report.load(str(p))
    assert ("a1", "prefix_probe") in d
    assert d[("a1", "prefix_probe")]["prefix_hit_rate"] == 0.5


def test_report_prefix_table_renders_both_sources():
    """prefix_lines joins serving prefix_probe rows with *-prefix traffic
    rows; rows missing any new key render dashes, never KeyError."""
    sys.path.insert(0, REPO)
    from benchmarks import report

    serving = [
        {"shape": "prefix_probe", "family": "transformer", "prefix_len": 64,
         "ttft_cold_s": 0.0639, "ttft_warm_s": 0.0199,
         "prefix_hit_rate": 0.38, "pages_in_use": 12, "evictions": 0},
        {"shape": "serve_decode_b2", "mode": "batched"},   # not a prefix row
    ]
    traffic = [
        {"mode": "traffic-virtual-prefix", "family": "transformer",
         "shared_prefix_len": 64, "ttft_cold_p50_s": 0.0191,
         "ttft_warm_p50_s": 0.0168, "prefix_hit_rate": 0.749,
         "pages_in_use": 27, "evictions": 0},
        {"mode": "traffic-virtual", "family": "transformer"},  # no prefix keys
    ]
    lines = report.prefix_lines(serving, traffic)
    probe = [l for l in lines if l.startswith("| probe")]
    traf = [l for l in lines if l.startswith("| traffic")]
    assert len(probe) == 1 and len(traf) == 1
    assert "63.9" in probe[0] and "19.9" in probe[0] and "3.21x" in probe[0]
    assert "19.1" in traf[0] and "16.8" in traf[0] and "virtual" in traf[0]
    # a traffic row missing the warm/cold keys but tagged -prefix still
    # renders (as dashes) rather than KeyError-ing
    lines2 = report.prefix_lines([], [{"mode": "traffic-wall-prefix"}])
    assert any("| traffic (wall)" in l and "—" in l for l in lines2)
    # and with no prefix rows anywhere the table is absent entirely
    assert report.prefix_lines([{"mode": "batched"}],
                               [{"mode": "traffic-virtual"}]) == []


# ---------------------------------------------------------------------------
# HLO parser corner cases (repro.analysis.hlo — benchmarks/hlo_analysis is
# the import shim over it)
# ---------------------------------------------------------------------------

# hand-written module fragments exercising the exact syntax the checkers
# key on; real lowerings around them are covered by test_audit.py
_ASYNC_AR_HLO = """\
HloModule m

%add_comb (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ars = f32[8] all-reduce-start(f32[8] %p0), to_apply=%add_comb
  ROOT %ard = f32[8] all-reduce-done(f32[8] %ars)
}
"""

# the same module as a TPU compile prints it: every type carries a tiled
# layout, whose parentheses and colons the instruction parser must skip
_L = "{1,0:T(4,128)(2,1)}"
_ASYNC_AR_HLO_TPU = f"""\
HloModule m

%add_comb (a: bf16[], b: bf16[]) -> bf16[] {{
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %add.1 = bf16[] add(bf16[] %a, bf16[] %b)
}}

ENTRY %main (p0: bf16[4,5120]{_L}) -> bf16[4,5120]{_L} {{
  %p0 = bf16[4,5120]{_L} parameter(0)
  %ars = bf16[4,5120]{_L} all-reduce-start(bf16[4,5120]{_L} %p0), to_apply=%add_comb
  ROOT %ard = bf16[4,5120]{_L} all-reduce-done(bf16[4,5120]{_L} %ars)
}}
"""


@pytest.mark.parametrize("text,nbytes", [(_ASYNC_AR_HLO, 32),
                                         (_ASYNC_AR_HLO_TPU, 4 * 5120 * 2)],
                         ids=["plain", "tpu_layout"])
def test_async_allreduce_start_done_counted_once(text, nbytes):
    """An async all-reduce appears as a -start/-done PAIR; both the
    loop-corrected census and the partial-sum gate must count the pair as
    ONE collective (the -start carries the combiner; double-counting would
    fail budgets that are actually met)."""
    from repro.analysis import hlo

    r = hlo.analyze(text)
    assert r["collectives"]["all-reduce"]["count"] == 1
    assert r["collectives"]["all-reduce"]["bytes"] == nbytes  # once
    ps = hlo.partial_sum_allreduces(text)
    assert ps["count"] == 1 and ps["bytes"] == nbytes


def test_variadic_tuple_combiner_is_partial_sum():
    """XLA's combiner pass merges several all-reduces into one variadic op
    whose reduction computation ROOTs a tuple OF adds — containment, not
    root-op equality, must classify it as a partial sum. A max combiner in
    the same module stays unclassified (argmax lowerings are not partial
    products)."""
    from repro.analysis import hlo

    text = """\
HloModule m

%var_comb (a0: f32[], b0: f32[], a1: f32[], b1: f32[]) -> (f32[], f32[]) {
  %a0 = f32[] parameter(0)
  %b0 = f32[] parameter(1)
  %a1 = f32[] parameter(2)
  %b1 = f32[] parameter(3)
  %add.a = f32[] add(f32[] %a0, f32[] %a1)
  %add.b = f32[] add(f32[] %b0, f32[] %b1)
  ROOT %t = (f32[], f32[]) tuple(%add.a, %add.b)
}

%max_comb (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %max.1 = f32[] maximum(f32[] %a, f32[] %b)
}

ENTRY %main (x: f32[16], y: f32[16]) -> (f32[16], f32[16]) {
  %x = f32[16] parameter(0)
  %y = f32[16] parameter(1)
  %armax = f32[16] all-reduce(f32[16] %x), to_apply=%max_comb
  ROOT %ar = (f32[16], f32[16]) all-reduce(f32[16] %x, f32[16] %y), to_apply=%var_comb
}
"""
    ps = hlo.partial_sum_allreduces(text)
    assert ps["count"] == 1, ps["ops"]           # the max combiner is not one
    assert ps["bytes"] == 128                    # both tuple halves counted
    assert ps["ops"][0][0].endswith("/ar")


def test_while_without_known_trip_count_counts_body_once():
    """A while op the compiler could not bound has no known_trip_count
    attribute; the multiplicity walk must fall back to trip=1 (body once,
    condition twice) rather than KeyError or drop the body's FLOPs — and
    the same module WITH the attribute scales exactly by it."""
    from repro.analysis import hlo

    tmpl = """\
HloModule m

%body (p: (f32[4,4], f32[4,4])) -> (f32[4,4], f32[4,4]) {
  %p = (f32[4,4], f32[4,4]) parameter(0)
  %c = f32[4,4] get-tuple-element((f32[4,4], f32[4,4]) %p), index=0
  %w = f32[4,4] get-tuple-element((f32[4,4], f32[4,4]) %p), index=1
  %d = f32[4,4] dot(f32[4,4] %c, f32[4,4] %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = (f32[4,4], f32[4,4]) tuple(%d, %w)
}

%cond (p: (f32[4,4], f32[4,4])) -> pred[] {
  %p = (f32[4,4], f32[4,4]) parameter(0)
  ROOT %k = pred[] constant(false)
}

ENTRY %main (x: (f32[4,4], f32[4,4])) -> (f32[4,4], f32[4,4]) {
  %x = (f32[4,4], f32[4,4]) parameter(0)
  ROOT %w.1 = (f32[4,4], f32[4,4]) while((f32[4,4], f32[4,4]) %x), condition=%cond, body=%body{TRIP}
}
"""
    body_flops = 2 * 4 * 4 * 4              # one 4x4 @ 4x4
    unbounded = tmpl.replace("{TRIP}", "")
    comps, entry = hlo.parse_computations(unbounded)
    mult = hlo._multiplicities(comps, entry)
    assert mult["body"] == 1.0 and mult["cond"] == 2.0
    assert hlo.analyze(unbounded)["dot_flops"] == body_flops

    bounded = tmpl.replace(
        "{TRIP}", ', backend_config={"known_trip_count":{"n":"12"}}')
    comps, entry = hlo.parse_computations(bounded)
    mult = hlo._multiplicities(comps, entry)
    assert mult["body"] == 12.0 and mult["cond"] == 13.0
    assert hlo.analyze(bounded)["dot_flops"] == 12 * body_flops


def test_hlo_shim_reexports_checkers():
    """benchmarks/hlo_analysis stays importable with its full pre-move
    surface — plus the new checkers — so stored scripts keep running."""
    sys.path.insert(0, REPO)
    from benchmarks import hlo_analysis
    from repro.analysis import hlo

    for name in ("analyze", "parse_computations", "partial_sum_allreduces",
                 "donation_aliases", "host_transfers", "dtype_audit",
                 "collective_budget", "_multiplicities"):
        assert getattr(hlo_analysis, name) is getattr(hlo, name), name


# ---------------------------------------------------------------------------
# report.py: serving-contract audit table
# ---------------------------------------------------------------------------

def test_report_audit_table_renders_and_tolerates_sparse_cells():
    """audit_lines renders the benchmarks/audit.py artifact; cells from
    older runs may lack closures/findings/summary and must render dashes,
    never KeyError. No artifact at all -> no table."""
    sys.path.insert(0, REPO)
    from benchmarks import report

    data = {
        "lint": [{"check": "jax-config-global", "where": "engine.py:381",
                  "detail": "x", "level": "error", "allowlisted": True}],
        "cells": [
            {"family": "transformer", "mode": "dense", "placement": "single",
             "status": "audited",
             "closures": {"decode": {"donation_aliases": 3,
                                     "host_transfers": 0,
                                     "partial_sum_allreduces": 0}},
             "findings": [{"check": "donation", "where": "decode",
                           "detail": "d", "level": "error",
                           "allowlisted": False}]},
            {"family": "griffin", "mode": "paged", "placement": "single",
             "status": "downgraded"},           # sparse legacy cell
        ],
        "summary": {"audited": 1, "downgraded": 1, "gating": 1},
    }
    lines = report.audit_lines(data)
    tr = [l for l in lines if l.startswith("| transformer")]
    gr = [l for l in lines if l.startswith("| griffin")]
    assert len(tr) == 1 and len(gr) == 1
    assert "| 1 | 3 | 0 | 0 | 1/0/0 |" in tr[0]
    assert "downgraded" in gr[0] and "—" in gr[0]
    assert any("0 gating, 1 allowlisted" in l for l in lines)
    assert any("1 audited + 1 downgrade-verified" in l for l in lines)
    assert report.audit_lines({}) == []
    assert report.audit_lines({"cells": []}) == []


def test_report_audit_data_tolerates_missing_and_broken_files(tmp_path):
    sys.path.insert(0, REPO)
    from benchmarks import report

    assert report.audit_data(str(tmp_path / "nope.json")) == {}
    p = tmp_path / "broken.json"
    p.write_text("{not json")
    assert report.audit_data(str(p)) == {}
    p.write_text(json.dumps([1, 2, 3]))       # wrong top-level type
    assert report.audit_data(str(p)) == {}
    p.write_text(json.dumps({"cells": [], "lint": []}))
    assert report.audit_data(str(p)) == {"cells": [], "lint": []}
