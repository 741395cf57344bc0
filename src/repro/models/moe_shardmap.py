"""Expert-parallel MoE dispatch via shard_map (beyond-paper §Perf item).

Problem (measured, EXPERIMENTS.md appendix): the jit/GSPMD capacity-dispatch
scatter cannot lower as an all-to-all — the partitioner all-reduces the whole
(E·C, d) dispatch buffer across data shards (deepseek-v2 prefill: 1,069 GB/
device/step).

Fix exploited here: under the CASCADE/TP layout, *tokens are replicated over
the model axis* (batch shards over data) while *experts are sharded over
model*. So no token movement is needed at all: each model rank selects the
assignments routed to ITS local experts, computes them, and the combine is a
single psum of gate-weighted (T_local, d) outputs over the model axis —
activation-sized, not buffer-sized.

Per-layer collective: T_local · d · 4 B (one all-reduce), vs the GSPMD path's
(E·C·d + T·k·d) — ~10× less for olmoe, more for deepseek.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.cascade import CascadeConfig
from repro.configs.base import ArchConfig
from repro.models.moe import route


def _local_moe(xf, router, *expert_args, cfg: ArchConfig, ccfg: CascadeConfig,
               model_axis: str, cap: int, fp4: bool):
    """Runs on ONE model rank: xf (T_loc, d) [same on every rank]; expert
    weights (dense or FP4 codes+scales) are this rank's local slice."""
    if fp4:
        from repro.core import quant
        cg, csg, cu, csu, cd, csd = expert_args
        deq = jax.vmap(lambda c, sc: quant.dequantize_weight(c, sc, ccfg.compute_dtype))
        wg, wu, wd = deq(cg, csg), deq(cu, csu), deq(cd, csd)
    else:
        wg, wu, wd = expert_args
    t, d = xf.shape
    e_loc = wg.shape[0]
    k = cfg.moe_top_k
    rank = lax.axis_index(model_axis)

    gates, idx = route(xf, router, cfg)                         # (T, k) global ids

    flat_e = idx.reshape(-1)                                    # (T*k,)
    local_id = flat_e - rank * e_loc                            # position among local experts
    mine = (local_id >= 0) & (local_id < e_loc)
    local_id = jnp.where(mine, local_id, 0)

    onehot = jax.nn.one_hot(local_id, e_loc, dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, local_id[:, None], 1)[:, 0]
    keep = mine & (pos < cap)
    dst = jnp.where(keep, local_id * cap + pos, e_loc * cap)    # OOB = drop

    xk = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((e_loc * cap, d), xf.dtype).at[dst].add(xk, mode="drop")
    buf = buf.reshape(e_loc, cap, d)

    def ff(w, x):  # dense expert weights (shard_map works on raw arrays)
        return jnp.einsum("ecd,edf->ecf", x.astype(ccfg.compute_dtype),
                          w.astype(ccfg.compute_dtype),
                          preferred_element_type=jnp.float32).astype(ccfg.compute_dtype)

    h = jax.nn.silu(ff(wg, buf).astype(jnp.float32))
    h = (h * ff(wu, buf).astype(jnp.float32)).astype(buf.dtype)
    out = ff(wd, h).reshape(e_loc * cap, d)

    got = jnp.take(out, jnp.minimum(dst, e_loc * cap - 1), axis=0)
    got = jnp.where(keep[:, None], got, 0.0)
    y_partial = jnp.sum((got.astype(jnp.float32)
                         * gates.reshape(-1)[:, None]).reshape(t, k, d), axis=1)
    # combine: each rank contributed only its local experts' outputs
    return lax.psum(y_partial, model_axis)


def moe_ffn_apply_ep(params: dict, x: jax.Array, cfg: ArchConfig,
                     ccfg: CascadeConfig, mesh, model_axis: str = "model",
                     batch_axes=("pod", "data")) -> jax.Array:
    """shard_map expert-parallel MoE FFN. x: (B, S, d); expert weights in
    ``params`` are dense ('train'/'bf16' mode) and sharded (E over model)."""
    b, s, d = x.shape
    baxes = tuple(a for a in batch_axes if a in mesh.shape)
    baxis = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    data_size = 1
    for a in baxes:
        data_size *= mesh.shape[a]
    t_local = (b * s) // max(data_size, 1)
    cap = max(8, -(-math.ceil(cfg.moe_capacity_factor * t_local * cfg.moe_top_k
                              / cfg.n_experts) // 8) * 8)

    fp4 = "codes" in params["wg"]
    if fp4:
        expert_args = (params["wg"]["codes"], params["wg"]["scale"],
                       params["wu"]["codes"], params["wu"]["scale"],
                       params["wd"]["codes"], params["wd"]["scale"])
    else:
        expert_args = (params["wg"]["w"], params["wu"]["w"], params["wd"]["w"])

    fn = functools.partial(_local_moe, cfg=cfg, ccfg=ccfg,
                           model_axis=model_axis, cap=cap, fp4=fp4)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(baxis, None),            # tokens: data-sharded, model-replicated
                  P(None, None))             # router replicated
                 + (P(model_axis, None, None),) * len(expert_args),  # EP weights
        out_specs=P(baxis, None),
        check_rep=False)

    xf = x.reshape(b * s, d)
    y = mapped(xf, params["router"], *expert_args)

    if "shared" in params:
        from repro.models import layers as L
        y = y + L.mlp_apply(params["shared"], xf, "swiglu", ccfg).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype)
