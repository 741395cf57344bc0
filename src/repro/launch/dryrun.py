import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production meshes, with no real hardware.

For each cell this proves:
  * the sharding config is coherent (GSPMD partitions the step without
    falling back to replication errors or unsupported collectives),
  * per-device memory fits (``compiled.memory_analysis()``),
  * and it extracts the roofline inputs (``compiled.cost_analysis()`` +
    collective bytes parsed from the compiled HLO).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun
"""

import argparse
import dataclasses
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import base as cfgbase
from repro.core.cascade import CascadeConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.optim.adamw import AdamW, AdamWState
from repro.train import loop as train_loop

# ---------------------------------------------------------------------------
# HLO collective-bytes parser (§Roofline: collective term)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "f8e4m3fn": 1,
    "f8e5m2": 1, "f4e2m1fn": 1,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (per-device) HLO text.

    Counts each op's output shape once (operand size ~= output size for
    gather/permute; for all-reduce output == operand). Ops inside while
    bodies appear once — the caller scales loop-resident ops by trip count
    (see benchmarks/roofline.py).
    """
    stats = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", ls)
        if not m:
            continue
        shape_str, opname = m.group(1), m.group(2)
        base = None
        for c in _COLLECTIVES:
            if opname == c or opname.startswith(c + "-start") or opname == c + "-done":
                base = c
                break
        if base is None:
            continue
        if opname.endswith("-done"):
            continue  # counted at -start
        b = _tensor_bytes(shape_str)
        stats[base]["count"] += 1
        stats[base]["bytes"] += b
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items() if isinstance(v, dict))
    return stats


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def build_step(arch_id: str, shape_name: str, ccfg: CascadeConfig,
               tp_policy: str = "cascade", dp_shard: str = "none",
               full_dp: bool = False, remat_policy: str = "dots",
               microbatches: int = 1):
    """full_dp: batch sharded over ALL mesh axes (pure data parallelism);
    combined with dp_shard='fsdp' this is FSDP/ZeRO-3 — weights stay sharded
    and GSPMD all-gathers them per layer inside the scan."""
    """Returns (fn, abstract_args, in_specs_builder) for the cell."""
    cfg, model = registry.load(arch_id)
    shape = cfgbase.SHAPES[shape_name]
    specs = cfgbase.input_specs(cfg, shape)
    batch_axes = ("pod", "data", "model") if full_dp else ("pod", "data")
    # tied-embedding archs keep a replicated table under cascade: a
    # d-sharded table would make the tied head contract over a sharded dim
    # (a partial-sum all-reduce the policy forbids)
    tied = cfg.tie_embeddings

    params_shape = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), ccfg))

    if shape.kind == "train":
        opt = AdamW()
        state_shape = jax.eval_shape(
            lambda: train_loop.TrainState(
                params=model.init_params(jax.random.PRNGKey(0), ccfg),
                opt=opt.init(model.init_params(jax.random.PRNGKey(0), ccfg)),
                step=jnp.int32(0)))
        step_fn = train_loop.make_train_step(model, ccfg, opt, remat=True,
                                             remat_policy=remat_policy,
                                             microbatches=microbatches)
        abstract = (state_shape, specs)

        def in_specs(mesh):
            pspecs = shd.param_specs(params_shape, tp_policy, tied_embed=tied)
            mspecs = pspecs
            if dp_shard in ("zero1", "fsdp"):
                mspecs = shd.add_data_dim(pspecs, params_shape, mesh)
            if dp_shard == "fsdp":
                pspecs = mspecs
            state_specs = train_loop.TrainState(
                params=pspecs,
                opt=AdamWState(step=P(), mu=mspecs, nu=mspecs),
                step=P())
            return (state_specs, shd.batch_specs(specs, batch_axes=batch_axes, mesh=mesh))

        return step_fn, abstract, in_specs

    if shape.kind == "prefill":
        def step_fn(params, batch):
            return model.prefill(params, batch, ccfg, max_len=shape.seq_len)

        abstract = (params_shape, specs)

        def in_specs(mesh):
            return (shd.param_specs(params_shape, tp_policy, tied_embed=tied),
                    shd.batch_specs(specs, mesh=mesh))

        return step_fn, abstract, in_specs

    # decode: one new token against a cache of seq_len
    cache_shape = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                 dtype=ccfg.resolved_kv_dtype))

    def step_fn(params, batch, cache):
        return model.decode_step(params, batch, cache, ccfg)

    abstract = (params_shape, specs, cache_shape)

    def in_specs(mesh):
        return (shd.param_specs(params_shape, tp_policy, tied_embed=tied),
                shd.batch_specs(specs, mesh=mesh),
                shd.cache_specs(cache_shape, mesh))

    return step_fn, abstract, in_specs


def lower_cell(arch_id: str, shape_name: str, mesh, ccfg=None,
               tp_policy: str = "cascade", verbose: bool = True,
               return_compiled: bool = False, act_policy: str = "cascade",
               dp_shard: str = "none", full_dp: bool = False,
               remat_policy: str = "dots", microbatches: int = 1,
               moe_ep: bool = False) -> dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    ccfg = ccfg or CascadeConfig(mode="serve_fp4" if "train" not in shape_name
                                 else "train", qat=False)
    cfg = registry.get_config(arch_id)
    shape = cfgbase.SHAPES[shape_name]
    if not cfgbase.shape_applicable(cfg, shape):
        return {"arch": arch_id, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch: long_500k requires sub-quadratic attention"}

    t0 = time.time()
    step_fn, abstract, in_specs_fn = build_step(arch_id, shape_name, ccfg, tp_policy,
                                                 dp_shard, full_dp, remat_policy,
                                                 microbatches)
    in_specs = in_specs_fn(mesh)
    in_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        in_specs, is_leaf=lambda x: isinstance(x, P))

    shd.set_activation_policy(mesh, act_policy, moe_ep=moe_ep)
    try:
        with mesh:
            lowered = jax.jit(step_fn, in_shardings=in_shardings).lower(*abstract)
            compiled = lowered.compile()
    finally:
        shd.clear_activation_policy()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    record = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "tp_policy": tp_policy,
        "act_policy": act_policy,
        "dp_shard": dp_shard,
        "full_dp": full_dp,
        "moe_ep": moe_ep,
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": cost.get("flops", -1.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", -1.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "collectives": coll,
    }
    if verbose:
        print(json.dumps(record, indent=None, default=str))
    if return_compiled:
        record["_compiled"] = compiled
    return record


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(cfgbase.SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--tp-policy", default="cascade", choices=["cascade", "megatron"])
    ap.add_argument("--act-policy", default="cascade",
                    choices=["none", "cascade", "seqpar", "fulldp"])
    ap.add_argument("--dp-shard", default="none", choices=["none", "zero1", "fsdp"])
    ap.add_argument("--full-dp", action="store_true")
    ap.add_argument("--remat-policy", default="dots", choices=["dots", "none", "save_all"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = list(registry.ALIASES.keys()) if (args.all or args.arch is None) else [args.arch]
    shapes = list(cfgbase.SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    records = []
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                try:
                    rec = lower_cell(arch, shape, mesh, tp_policy=args.tp_policy,
                                     act_policy=args.act_policy, dp_shard=args.dp_shard,
                                     full_dp=args.full_dp, remat_policy=args.remat_policy)
                except Exception as e:  # a failure here is a bug in the system
                    rec = {"arch": arch, "shape": shape, "mesh": dict(mesh.shape),
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    print(json.dumps(rec, default=str))
                records.append(rec)

    if args.out:
        import os as _os
        _os.makedirs(_os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1, default=str)
    n_fail = sum(r["status"] == "FAILED" for r in records)
    print(f"\n{len(records)} cells: {len(records) - n_fail} ok/skipped, {n_fail} FAILED",
          file=sys.stderr)
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
