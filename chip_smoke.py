#!/usr/bin/env python3
"""Smoke run of the FP4 serving path on a TPU: the quickest proof that the
system still starts on the chip. It is a correctness check, not a
benchmark; every time it prints is wall time with compilation included.

    python chip_smoke.py             # one chip: codeqwen1.5-7b, full width
    python chip_smoke.py --chips 4   # 1x4 mesh: qwen2.5-32b, cascade vs megatron

One chip: random FP4 weights (seeded) are drawn on the device at the
published width of codeqwen1.5-7b, and ``ServeEngine`` serves the same
requests twice — through the jnp FP4 path and through the fused Pallas
kernels (``ServeConfig(fused=True)``, compiled, not interpreted). The two
paths are compared on logits under teacher forcing.

``--chips 4``: qwen2.5-32b (about 17.5 GB of FP4 weights, more than one
chip holds), initialized already sharded on a (data=1, model=4) mesh,
served under the cascade and the megatron placements; the two are compared
on logits and on their decode steps' partial-sum all-reduce counts.

Exits non-zero, printing no result, unless JAX's first device is a TPU. The
last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import hlo  # noqa: E402
from repro.core.cascade import CascadeConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.serve.engine import Request, ServeConfig, ServeEngine  # noqa: E402

SEED = 0
ONE_CHIP = dict(arch="codeqwen1.5-7b", requests=8, prompt_len=512,
                max_new=32, max_batch=8, forced_steps=16)
FOUR_CHIP = dict(arch="qwen2.5-32b", requests=4, prompt_len=128,
                 max_new=8, max_batch=4, forced_steps=16)

# Tolerances on logits, as max|a - b| (abs) and max|a - b| / max|b| (rel).
# fused vs jnp: the jnp path rounds every dequantized weight (code * scale)
# to bf16 before its dot, the fused kernel dots the exact FP4 values in bf16
# and applies the scale to the f32 product; both round each layer's outputs
# to bf16, so the paths drift apart by bf16 rounding over the layers.
FUSED_ABS_TOL = 0.25
FUSED_REL_TOL = 0.05
# cascade vs megatron: same jnp arithmetic, but megatron's row-parallel
# layers sum their K-shards' f32 partial products across chips in another
# order before the bf16 cast, which flips the odd bf16 rounding.
MESH_ABS_TOL = 0.25
MESH_REL_TOL = 0.05
#: what a compiled Pallas kernel leaves in the step's HLO
KERNEL_CALL = "tpu_custom_call"


def check(ok, what) -> None:
    """Fail the run (non-zero exit through the traceback) unless ``ok``;
    unlike ``assert`` it is not stripped under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu(chips: int):
    """The devices, or exit non-zero: this script never runs on the CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs


def make_prompts(vocab: int, n: int, length: int, seed: int = SEED) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, vocab, size=(n, length)).astype(np.int32)


def forced_logits(eng: ServeEngine, tokens: np.ndarray) -> np.ndarray:
    """Teacher forcing through the engine's own compiled decode step: from
    an empty cache, slot b is fed ``tokens[b, t]`` at step t whatever the
    model predicted. Returns the (B, T, V) f32 logits of every step."""
    cache = jax.tree.map(
        lambda x: jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding),
        eng.cache)
    out = []
    for t in range(tokens.shape[1]):
        logits, cache = eng.call_step("decode", eng.params,
                                      jnp.asarray(tokens[:, t:t + 1]), cache)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out, axis=1)


def serve(eng: ServeEngine, prompts: np.ndarray, max_new: int) -> tuple[list, float]:
    """Serve one request per prompt to completion; (token lists, seconds)."""
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    while eng.busy():
        eng.step()
    return [r.tokens_out for r in reqs], time.perf_counter() - t0


def check_served(tokens: list, n: int, max_new: int, vocab: int):
    check(len(tokens) == n, f"{len(tokens)} of {n} requests finished")
    for i, toks in enumerate(tokens):
        check(len(toks) == max_new, f"request {i}: {len(toks)} tokens")
        check(all(0 <= t < vocab for t in toks), f"request {i}: {toks}")


def compare(name: str, got: np.ndarray, ref: np.ndarray, abs_tol: float,
            rel_tol: float) -> dict:
    check(np.isfinite(ref).all() and np.isfinite(got).all(), f"{name}: non-finite logits")
    err = float(np.max(np.abs(got - ref)))
    rel = err / float(np.max(np.abs(ref)))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    print(f"{name}: logits {ref.shape}, max abs err {err!r} (tol {abs_tol}), "
          f"max rel err {rel!r} (tol {rel_tol}), argmax agreement {agree!r}")
    check(err <= abs_tol and rel <= rel_tol, f"{name}: logits outside tolerance")
    return {"max_abs": err, "max_rel": rel, "argmax_agreement": agree}


def serve_config(c: dict, **kw) -> ServeConfig:
    return ServeConfig(max_batch=c["max_batch"],
                       max_len=c["prompt_len"] + c["max_new"] + 1, **kw)


def one_chip(c: dict = ONE_CHIP, smoke: bool = False,
             compute_dtype=jnp.bfloat16) -> dict:
    """Build ``c["arch"]`` in serve_fp4, serve through the jnp and the fused
    path, assert the fused one compiled its kernels, and compare logits."""
    cfg, model = registry.load(c["arch"], smoke=smoke)
    ccfg = CascadeConfig(mode="serve_fp4", compute_dtype=compute_dtype)
    t0 = time.perf_counter()
    params = jax.block_until_ready(registry.init_params(model, ccfg, seed=SEED))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; FP4 params {nbytes} bytes, drawn "
          f"on device in {time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(cfg.vocab, c["requests"], c["prompt_len"])
    forced = prompts[:c["max_batch"], :c["forced_steps"]]
    out = {}
    for fused in (False, True):
        eng = ServeEngine(model, params, ccfg, serve_config(c, fused=fused))
        m = eng.metrics()
        check(not m["downgrades"], m["downgrades"])
        check(m["fused"] == fused and m["effective_mode"].endswith("-fused") == fused, m)
        path = m["effective_mode"]
        t0 = time.perf_counter()
        text = eng.lower_step("decode").as_text()
        print(f"{path}: decode step compiled in {time.perf_counter() - t0:.1f} s, "
              f"{KERNEL_CALL} x{text.count(KERNEL_CALL)}")
        if fused:
            check(KERNEL_CALL in text, "fused decode step has no compiled kernel")
        out[path] = {"logits": forced_logits(eng, forced)}
        tokens, secs = serve(eng, prompts, c["max_new"])
        check_served(tokens, c["requests"], c["max_new"], cfg.vocab)
        out[path]["tokens"] = tokens
        print(f"{path}: served {len(tokens)} requests x {c['prompt_len']} "
              f"prompt tokens, {sum(map(len, tokens))} tokens out in "
              f"{secs:.1f} s wall (compilation included)")
        del eng
        gc.collect()
    (jnp_path, ref), (fused_path, got) = out.items()
    res = compare(f"{fused_path} vs {jnp_path}", got["logits"], ref["logits"],
                  FUSED_ABS_TOL, FUSED_REL_TOL)
    same = float(np.mean(np.asarray(got["tokens"]) == np.asarray(ref["tokens"])))
    print(f"served streams: {same!r} of tokens identical across the two paths")
    return res


def four_chip(devices, c: dict = FOUR_CHIP, smoke: bool = False,
              compute_dtype=jnp.bfloat16) -> dict:
    """``c["arch"]`` on a (data=1, model=4) mesh under the cascade and the
    megatron placements (jnp FP4 path: fused x mesh is a recorded
    downgrade); compare logits and partial-sum all-reduce counts."""
    cfg, model = registry.load(c["arch"], smoke=smoke)
    ccfg = CascadeConfig(mode="serve_fp4", compute_dtype=compute_dtype)
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab} on mesh {dict(mesh.shape)}; jnp FP4 "
          f"path (Pallas kernels under a mesh are not supported yet)")
    prompts = make_prompts(cfg.vocab, c["requests"], c["prompt_len"])
    forced = prompts[:c["max_batch"], :c["forced_steps"]]
    out = {}
    for policy in ("cascade", "megatron"):
        t0 = time.perf_counter()
        params = jax.block_until_ready(registry.init_params(
            model, ccfg, seed=SEED, mesh=mesh, tp_policy=policy))
        total = sum(x.nbytes for x in jax.tree.leaves(params))
        dev0 = sum(s.data.nbytes for x in jax.tree.leaves(params)
                   for s in x.addressable_shards if s.device == devices[0])
        print(f"{policy}: FP4 params {total} bytes, {dev0} on device 0, drawn "
              f"sharded in {time.perf_counter() - t0:.1f} s")
        check(dev0 < total / 2, "params were not sharded")
        eng = ServeEngine(model, params, ccfg,
                          serve_config(c, tp_policy=policy), mesh=mesh)
        check(not eng.downgrades, eng.downgrades)
        ar = hlo.partial_sum_allreduces(eng.decode_step_hlo())["count"]
        print(f"{policy}: decode step partial-sum all-reduces {ar}")
        out[policy] = {"allreduces": ar, "logits": forced_logits(eng, forced)}
        tokens, secs = serve(eng, prompts, c["max_new"])
        check_served(tokens, c["requests"], c["max_new"], cfg.vocab)
        print(f"{policy}: served {len(tokens)} requests, "
              f"{sum(map(len, tokens))} tokens out in {secs:.1f} s wall "
              f"(compilation included)")
        del eng, params
        gc.collect()
    check(out["cascade"]["allreduces"] == 0, out["cascade"]["allreduces"])
    check(out["megatron"]["allreduces"] > 0, out["megatron"]["allreduces"])
    return compare("cascade vs megatron", out["cascade"]["logits"],
                   out["megatron"]["logits"], MESH_ABS_TOL, MESH_REL_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 1x4 mesh phase (qwen2.5-32b)")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    print(f"device: {devs[0].device_kind}, {len(devs)} visible")
    print(f"compilation cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip(devs)
    else:
        one_chip()
    for d in devs[:args.chips]:
        peak = d.memory_stats()["peak_bytes_in_use"]
        print(f"peak HBM in use on {d}: {peak} bytes")
        check(peak < 16e9, "peak HBM above 16 GB")
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
