"""The system under test, built from a cell's files, and the benchmark's
own driver of it.

The served path is the one ``repro.launch.serve`` serves: the program's
model that the configuration's family builds (``bench/families/
<model_type>.py``), in ``serve_fp4`` under ``ServeEngine``, driven through
``submit()`` and ``step()``. The driver is derived from
``SLORouter.run_trace`` (one replica, no kills): arrivals are submitted
when due, stamped with the time they were due, and the engine steps while
it has work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from bench import cells
from bench import traffic as T
from bench.weights import make_params


def model_config(cell, size: Optional[dict] = None) -> dict:
    """The configuration as run: the file's keys, with ``size`` (a test's
    smaller shapes) laid over them."""
    cfg = dict(cell.config)
    cfg.update(size or {})
    return cfg


def build_engine(cell, cfg: dict, seed: int, devices):
    """(engine, params): the cell's model with weights drawn from ``seed``,
    under ``ServeEngine`` with the cell's slot grid, on ``devices``. The
    model comes from the family that ``cfg["model_type"]`` names."""
    import jax.numpy as jnp
    from repro.core.cascade import CascadeConfig
    from repro.serve.engine import ServeConfig, ServeEngine

    family = cells.family(cfg["model_type"])
    model = family.model(cell.config_name, cfg)
    serve = cell.config["serve"]
    dtype = getattr(jnp, serve["compute_dtype"])
    ccfg = CascadeConfig(mode=serve["mode"], compute_dtype=dtype,
                         kv_dtype=getattr(jnp, serve["kv_dtype"]))
    mesh, shardings_fn = None, None
    if cell.config.get("mesh"):
        from repro.distributed.sharding import serving_param_shardings
        from repro.launch.mesh import make_mesh
        m = cell.config["mesh"]
        mesh = make_mesh((m["data"], m["model"]), ("data", "model"),
                         devices=devices[: m["data"] * m["model"]])
        shardings_fn = lambda tree: serving_param_shardings(tree, mesh, m["tp_policy"])
    params = make_params(model, ccfg, seed, shardings_fn,
                         getattr(family, "fill", None))
    w = cell.workload
    scfg = ServeConfig(max_batch=w["max_batch"], max_len=w["max_len"],
                       prefill_chunk=serve["prefill_chunk"],
                       token_budget=serve["token_budget"],
                       temperature=serve["temperature"], eos_id=serve["eos_id"],
                       fused=serve["fused"],
                       tp_policy=(cell.config.get("mesh") or {}).get("tp_policy", "cascade"))
    engine = ServeEngine(model, params, ccfg, scfg, mesh=mesh)
    if engine.downgrades:
        raise RuntimeError(f"the engine runs another path than configured: "
                           f"{engine.downgrades}")
    return engine, params


@dataclasses.dataclass
class Step:
    start: float
    end: float
    produced: int          # decode tokens committed (= active slots)
    decode_s: float        # host time of the decode half (0: no decode)


@dataclasses.dataclass
class Drive:
    """What one run did: its requests, its steps and its window."""
    requests: list                       # engine Requests, in due order
    due: np.ndarray                      # absolute due time of each request
    steps: List[Step] = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)           # host clock, open and close
    traced: Optional[tuple] = None       # host interval of the trace
    lateness: List[float] = dataclasses.field(default_factory=list)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]


def _step(engine, drive: Drive):
    n = len(engine.step_times)
    t0 = time.monotonic()
    produced = engine.step()
    t1 = time.monotonic()
    dec = engine.step_times[-1] if len(engine.step_times) > n else 0.0
    drive.steps.append(Step(t0, t1, produced, dec))


def warm_up(engine, vocab: int, seed: int, prompt_len: int):
    """Compile every program the window uses: one request through chunked
    prefill (extend), the slot write and decode."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(T.seed_sequence(seed, 3))
    engine.submit(Request(uid=-1, max_new_tokens=2,
                          prompt=rng.integers(0, vocab, prompt_len, dtype=np.int32)))
    while engine.busy():
        engine.step()


def drive(engine, planned: List[T.Planned], traffic: dict, seconds: float,
          trace_fn=None, trace_seconds: float = 0.0) -> Drive:
    """Run the window. A ``backlog`` mix submits everything at once and fills
    the slot grid before the window opens; a ``poisson`` mix offers each
    request when due, from ``lead_s`` before the window opens. After the
    window closes, an open-loop run keeps stepping (no new arrivals) until
    every request that arrived in the window has its first token.

    ``trace_fn(start: bool)`` starts and stops the profiler over the last
    ``trace_seconds`` of the window."""
    from repro.serve.engine import Request
    reqs = [Request(uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new_tokens)
            for p in planned]
    due_rel = np.array([p.due_s for p in planned])
    open_loop = traffic["arrival"] == "poisson"
    now = time.monotonic()
    if open_loop:
        t_open = now + traffic["lead_s"]
    else:
        for r in reqs:
            engine.submit(r)
        d = Drive(reqs, np.full(len(reqs), now))
        while engine.queue and any(s is None for s in engine.slots):
            _step(engine, d)
        t_open = time.monotonic()
    d = Drive(reqs, t_open + due_rel if open_loop else np.full(len(reqs), now))
    t_close = t_open + seconds
    t_trace = t_close - trace_seconds if trace_fn else None
    nxt = 0
    while True:
        now = time.monotonic()
        if t_trace is not None and now >= t_trace and d.traced is None:
            trace_fn(True)
            d.traced = (time.monotonic(), None)
        if open_loop:
            while nxt < len(reqs) and d.due[nxt] <= now:
                reqs[nxt].created_at = d.due[nxt]
                engine.submit(reqs[nxt])
                if d.due[nxt] >= t_open:
                    d.lateness.append(now - d.due[nxt])
                nxt += 1
        if now >= t_close:
            break
        if engine.busy():
            _step(engine, d)
        elif open_loop:             # idle until the next arrival or the close
            time.sleep(max(0.0, min(d.due[nxt] if nxt < len(reqs) else t_close,
                                    t_close) - now))
        else:
            raise RuntimeError("the backlog ran dry inside the window")
    d.window = (t_open, now)
    if d.traced is not None:
        d.traced = (d.traced[0], time.monotonic())
        trace_fn(False)
    if open_loop:
        waiting = [r for r, t in zip(reqs, d.due) if d.in_window(t)]
        while not all(r.first_token_at for r in waiting):
            _step(engine, d)
    return d
