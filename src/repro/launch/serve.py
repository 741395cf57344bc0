"""Serving launcher: FP4 weights, continuous batching, optional CREST,
mesh-native sharded decode.

CPU smoke:  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b \
                --smoke --requests 16 --prompt-len 12 --max-new 8

Host-mesh demo (8 virtual CPU devices, CASCADE column-parallel params +
slot-sharded caches; token-exact with the single-device run):

    PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b \
        --smoke --requests 8 --max-batch 4 --host-devices 8 --mesh 4x2

``--tp-policy megatron`` serves the row+column baseline (its decode step
carries the partial-sum all-reduces CASCADE abolishes — compare with
``--verify-hlo``, which prints the partial-sum all-reduce count of the
compiled decode step and fails if a cascade-policy step has any).

``--audit`` runs the full serving-contract auditor (repro.analysis) over
every step closure of the engine as configured — the launcher-local slice
of what ``python -m benchmarks.audit`` checks matrix-wide in CI.
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core.cascade import CascadeConfig
from repro.models import registry
from repro.serve import telemetry as T
from repro.serve.engine import Request, ServeConfig, ServeEngine


def phase_summary(m: dict) -> str:
    """One line of an engine's phase counters (``metrics()["phases"]``):
    the share of step time spent in admission, the host's wait for each
    decode step's picks, the step time outside the host's waits for the
    device (decode and first-token syncs), and compilations."""
    ph = m["phases"]

    def secs(name):
        return ph.get(name, {}).get("seconds", 0.0)

    steps = max(ph.get(T.STEP, {}).get("calls", 0), 1)
    step_s = max(secs(T.STEP), 1e-12)
    host_s = step_s - secs(T.DECODE_SYNC) - secs(T.FIRST_TOKEN)
    return (f"phases: admission {100 * secs(T.ADMIT) / step_s:.1f}% of step "
            f"time, decode sync {1e3 * secs(T.DECODE_SYNC) / steps:.2f} ms/step, "
            f"host {1e3 * host_s / steps:.2f} ms/step, compiles {m['compiles']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--no-fp4", action="store_true", help="serve bf16 baseline")
    ap.add_argument("--fused", action="store_true",
                    help="route decode/extend/verify through the Pallas "
                         "kernels (packed-FP4 matmul + decode attention); "
                         "needs FP4 params — incompatible with --no-fp4 and "
                         "--mesh (downgrades with a warning)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="serve from the paged KV pool with the radix-tree "
                         "prefix cache: shared prompt prefixes admit "
                         "through already-resident pages instead of "
                         "re-prefilling (token-exact with the dense "
                         "engine; ring/recurrent archs downgrade with a "
                         "warning)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page under --prefix-cache")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="> 0 enables seeded sampling (default: greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k best logits (0 = all)")
    ap.add_argument("--draft-len", type=int, default=0,
                    help="speculative decode: K drafted tokens per slot per "
                         "step (0 = off). Composes with --temperature: "
                         "sampled serving runs speculative sampling "
                         "(rejection resampling, distribution-exact with "
                         "plain sampled decode)")
    ap.add_argument("--ngram-max", type=int, default=3,
                    help="longest suffix n-gram the prompt-lookup drafter matches")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual CPU devices (must be set before "
                         "first jax use; the CI/laptop stand-in for a mesh)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data, model) mesh, e.g. 4x2 ('auto' "
                         "splits the available devices)")
    ap.add_argument("--tp-policy", default="cascade",
                    choices=["cascade", "megatron"],
                    help="param placement when --mesh is set")
    ap.add_argument("--verify-hlo", action="store_true",
                    help="print the decode step's partial-sum all-reduce "
                         "count; exit 1 if a cascade-policy step has any")
    ap.add_argument("--audit", action="store_true",
                    help="audit EVERY jitted step closure of the "
                         "constructed engine against the serving contract "
                         "(donation, host transfers, dtypes, collective "
                         "budget — repro.analysis.contract) and exit 1 on "
                         "any error finding; composes with --mesh/--fused/"
                         "--prefix-cache to audit exactly what would serve")
    ap.add_argument("--traffic", action="store_true",
                    help="live-traffic demo: route a seeded open-loop "
                         "Poisson trace (--rate, --requests arrivals) over "
                         "--replicas engine replicas via the SLO-aware "
                         "router, and print per-request TTFT/inter-token "
                         "percentiles + SLO attainment")
    ap.add_argument("--replicas", type=int, default=2,
                    help="engine replicas under --traffic")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (requests/s) under --traffic")
    ap.add_argument("--slo-ttft", type=float, default=0.5,
                    help="per-request TTFT SLO seconds under --traffic "
                         "(0 = none)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="admission deadline seconds under --traffic "
                         "(0 = never shed)")
    ap.add_argument("--kill-at", type=float, default=None, metavar="T",
                    help="with --traffic: fail replica 0 in place T seconds "
                         "into the trace (its streams re-route token-exact)")
    args = ap.parse_args()

    from repro.launch import mesh as meshlib
    from repro.launch.compile_cache import enable_compile_cache
    if args.host_devices:
        meshlib.force_host_device_count(args.host_devices)
    enable_compile_cache()

    import jax
    mesh = meshlib.make_serving_mesh(args.mesh) if args.mesh else None
    cfg, model = registry.load(args.arch, smoke=args.smoke)
    compute = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    # random weights drawn on the device straight into the served format
    # (packed FP4 unless --no-fp4), already placed on the mesh if any
    ccfg = CascadeConfig(mode="train" if args.no_fp4 else "serve_fp4",
                         compute_dtype=compute)
    params = registry.init_params(model, ccfg, seed=0, mesh=mesh,
                                  tp_policy=args.tp_policy)

    scfg = ServeConfig(max_batch=args.max_batch,
                       max_len=args.prompt_len + args.max_new + 1,
                       temperature=args.temperature, top_k=args.top_k,
                       draft_len=args.draft_len, ngram_max=args.ngram_max,
                       tp_policy=args.tp_policy, fused=args.fused,
                       prefix_cache=args.prefix_cache,
                       page_size=args.page_size)

    if args.traffic:
        if mesh is not None or args.verify_hlo:
            print("--traffic is a replica-fleet demo; run it without "
                  "--mesh/--verify-hlo")
            raise SystemExit(2)
        from repro.serve.elastic import ReplicaSet
        from repro.serve.router import SLORouter
        from repro.serve.traffic import TrafficConfig, poisson_trace
        engines = [ServeEngine(model, params, ccfg, scfg)
                   for _ in range(args.replicas)]
        rs = ReplicaSet(engines)
        router = SLORouter(rs)
        trace = poisson_trace(TrafficConfig(
            rate_rps=args.rate, n_requests=args.requests,
            prompt_lens=((max(1, args.prompt_len // 2), args.prompt_len),),
            output_lens=((max(1, args.max_new // 2), args.max_new),),
            vocab=cfg.vocab, slo_ttft_s=args.slo_ttft,
            deadline_s=args.deadline))
        kills = [(args.kill_at, 0)] if args.kill_at is not None else []
        t0 = time.time()
        recs = router.run_trace(trace, kills=kills)
        dt = time.time() - t0
        m = router.metrics()
        print(f"traffic: {m['requests_offered']} arrivals at "
              f"{args.rate:g} req/s over {args.replicas} replicas "
              f"({m['replicas_alive']} alive after "
              f"{len(kills)} kill(s)) in {dt:.2f}s")
        print(f"  ttft p50/p99 {m['ttft_p50_s']*1e3:.1f}/"
              f"{m['ttft_p99_s']*1e3:.1f} ms, inter-token p50/p99 "
              f"{m['inter_token_p50_s']*1e3:.1f}/"
              f"{m['inter_token_p99_s']*1e3:.1f} ms")
        print(f"  SLO attainment {m['slo_attainment']:.3f} "
              f"(finished {m['requests_finished']}, shed "
              f"{m['requests_shed']}, rejected {m['requests_rejected']})")
        for r in recs[:3]:
            print(f"  req {r.uid}: {r.tokens_out}")
        return

    eng = ServeEngine(model, params, ccfg, scfg, mesh=mesh)

    # never let "nothing was checked" look like "the invariant holds"
    if args.verify_hlo and mesh is None:
        print("--verify-hlo requires --mesh: a single-device decode step "
              "has no collectives, so its zero verifies nothing")
        raise SystemExit(2)
    if args.verify_hlo and not eng.batched:
        print("--verify-hlo requires the batched engine; this model fell "
              "back to the slot-wise path, nothing was verified")
        raise SystemExit(2)
    if args.audit:
        from repro.analysis import contract, format_findings, gating
        res = contract.audit_engine(eng)
        for name, st in res["closures"].items():
            print(f"audit {name}: aliases={st['donation_aliases']} "
                  f"host_xfer={st['host_transfers']} "
                  f"psum_ar={st['partial_sum_allreduces']} "
                  f"packed_params={st['packed_params']}")
        bad = gating(res["findings"])
        if res["findings"]:
            print(format_findings(res["findings"]))
        print(f"audit: {len(res['closures'])} closure(s), "
              f"{len(res['findings'])} finding(s), {len(bad)} gating")
        if bad:
            print("SERVING CONTRACT VIOLATED", flush=True)
            raise SystemExit(1)

    if args.verify_hlo:
        try:
            from benchmarks import hlo_analysis
        except ImportError:
            print("--verify-hlo needs benchmarks/ on the path (run from the "
                  "repo root)")
            raise SystemExit(2)
        ar = hlo_analysis.partial_sum_allreduces(eng.decode_step_hlo())
        print(f"decode-step partial-sum all-reduces: {ar['count']} "
              f"({ar['bytes']} B) under tp_policy={args.tp_policy}")
        if args.tp_policy == "cascade" and ar["count"]:
            print("CASCADE invariant VIOLATED", flush=True)
            raise SystemExit(1)
        if eng.spec:
            # with --temperature > 0 this lowers the FUSED sampled
            # verify+accept/resample step the engine actually dispatches
            arv = hlo_analysis.partial_sum_allreduces(
                eng.decode_step_hlo("verify"))
            print(f"spec-verify partial-sum all-reduces: {arv['count']} "
                  f"({arv['bytes']} B) under tp_policy={args.tp_policy}")
            if args.tp_policy == "cascade" and arv["count"]:
                print("CASCADE invariant VIOLATED (spec verify)", flush=True)
                raise SystemExit(1)

    rng = np.random.default_rng(0)
    # under --prefix-cache the demo requests share their first half (the
    # "same system prompt, different user turn" shape the radix cache
    # serves): every admission after the first re-pins the resident pages
    shared = (rng.integers(0, cfg.vocab,
                           size=args.prompt_len // 2).astype(np.int32)
              if args.prefix_cache and args.prompt_len >= 2 else None)
    tail = args.prompt_len - (len(shared) if shared is not None else 0)

    def _prompt():
        p = rng.integers(0, cfg.vocab, size=tail).astype(np.int32)
        return p if shared is None else np.concatenate([shared, p])

    reqs = [Request(uid=i, prompt=_prompt(), max_new_tokens=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        eng.submit(r)

    t0 = time.time()
    total = 0
    while eng.busy():
        total += eng.step()
    dt = time.time() - t0
    m = eng.metrics()
    spec = (f", spec draft_len={m['draft_len']} "
            f"accepted/step={m['accepted_per_step']:.2f}" if m["spec"] else "")
    mstr = (f", mesh={m['mesh']} tp={m['tp_policy']}" if m["mesh"] else "")
    print(f"mode={m['effective_mode']}"
          + (f" (downgraded: {'; '.join(m['downgrades'])})"
             if m["downgrades"] else ""))
    print(f"served {args.requests} requests, {total} tokens in {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s), p99 step {eng.straggler_p99()*1e3:.1f} ms, "
          f"batched={m['batched']}{spec}{mstr}, admission wait {m['admission_wait_s_mean']*1e3:.1f} ms")
    print("  " + phase_summary(m))
    if m["paged"]:
        print(f"  prefix cache: hit rate {m['prefix_hit_rate']:.2f} "
              f"({m['prefix_hits']}/{m['prefix_lookups']} tokens), "
              f"pages {m['pages_in_use']}/{m['pages_total']} "
              f"(page_size={m['page_size']}), evictions {m['evictions']}")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {r.tokens_out}")


if __name__ == "__main__":
    main()
