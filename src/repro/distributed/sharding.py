"""Sharding policies: CASCADE column-parallel (paper-faithful) vs
Megatron-style row+column TP (baseline).

The paper's central distribution claim (Sections 2.2, 13.5): partial-sum
transfers dominate interconnect traffic in conventional distributed
inference; CASCADE eliminates them by making the **output-column dimension
the unit of parallelism** and keeping every reduction local. On a TPU mesh:

* ``cascade`` policy — every weight is sharded on its OUTPUT dim over
  ``model`` — including expert weights, whose unit of parallelism is the
  output column rather than the expert index. Activations are all-gathered
  (linear in d_model) between layers; **no all-reduce of partial sums
  exists anywhere in the graph**.
* ``megatron`` policy — the classic pairing: first matmul column-sharded,
  second matmul row-sharded, followed by an all-reduce of partial sums
  (quadratic-width accumulator traffic — what the paper abolishes); expert
  weights are expert-parallel (E over ``model``), the conventional MoE
  layout.

The dry-run roofline quantifies the collective-bytes difference between the
two policies for every (arch x shape) cell, and ``ServeEngine`` (see
``serve/engine.py``) places live serving params with these same policies —
the cascade decode step carries an executable zero-partial-sum-all-reduce
assertion (``benchmarks/hlo_analysis.partial_sum_allreduces``).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

# modules whose 2D weight contracts on dim 0 and expands on dim 1
_COLUMN_MODULES = {
    "wq", "wk", "wv", "w_gate", "w_up", "w_in", "wa", "wx", "in_proj",
    "wq_a", "wq_b", "wkv_a", "wkv_b", "lm_head",
}
# modules whose OUTPUT returns to d_model (Megatron shards these on dim 0)
_ROW_MODULES = {"wo", "w_down", "w_out", "out_proj"}
_EXPERT_MODULES = {"wg", "wu", "wd"}


def _leading_nones(n: int) -> tuple:
    return (None,) * n


def spec_for_param(path: tuple[str, ...], leaf, policy: str, model_axis: str = "model",
                   tied_embed: bool = False):
    """PartitionSpec for one param leaf, by (module name, leaf name, ndim)."""
    names = [p for p in path]
    leaf_name = names[-1] if names else ""
    module = names[-2] if len(names) >= 2 else ""
    ndim = leaf.ndim

    def pad(spec: tuple) -> P:
        return P(*(_leading_nones(ndim - len(spec)) + spec))

    # experts: (.., E, K, N) / codes (.., E, K//2, N) / scale (.., E, G, N).
    # cascade shards the OUTPUT column N (the paper's unit of parallelism —
    # every expert contraction stays local, combine is gather-only);
    # megatron keeps conventional expert parallelism (E over model).
    if module in _EXPERT_MODULES:
        if ndim >= 3:
            shard3 = ((model_axis, None, None) if policy == "megatron"
                      else (None, None, model_axis))
            return P(*(_leading_nones(ndim - 3) + shard3))
        return pad((None,))

    if leaf_name == "table":  # embedding (V, d)
        if policy == "megatron":
            return pad((model_axis, None))
        if tied_embed:
            # tied head: logits = x @ table.T contracts over d — a d-sharded
            # table would partial-sum the head matmul, so the table stays
            # replicated (memory for zero interconnect, the CASCADE trade)
            return pad((None, None))
        return pad((None, model_axis))

    if module == "router":
        return pad((None, None))

    is_linear = module in _COLUMN_MODULES or module in _ROW_MODULES
    if is_linear and leaf_name in ("w", "codes"):
        if policy == "megatron" and module in _ROW_MODULES:
            return pad((model_axis, None))
        return pad((None, model_axis))
    if is_linear and leaf_name == "scale":   # FP4 quant scales (G, N)
        if policy == "megatron" and module in _ROW_MODULES:
            return pad((None, None))
        return pad((None, model_axis))
    if is_linear and leaf_name == "b":
        if policy == "megatron" and module in _ROW_MODULES:
            return pad((None,))
        return pad((model_axis,))

    # norms, convs, gates, scalars: replicated
    return P(*_leading_nones(ndim))


def _path_names(path) -> tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def param_specs(params_tree: Any, policy: str = "cascade", model_axis: str = "model",
                tied_embed: bool = False):
    """PartitionSpec tree mirroring ``params_tree`` (arrays or SDS leaves).

    ``tied_embed`` marks archs whose lm_head is the embedding transpose
    (mamba2, phi4): their table stays replicated under cascade so the tied
    head matmul never contracts over a sharded dim.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_param(_path_names(path), leaf, policy,
                                          model_axis, tied_embed),
        params_tree)


def filter_divisible(specs_tree: Any, shapes_tree: Any, mesh):
    """Drop mesh-axis names from dims the axis size does not divide.

    Smoke-sized serving shapes (and batch-1 staging caches) routinely have
    dims smaller than a mesh axis; replicating those leaves keeps placement
    well-defined without per-arch divisibility bookkeeping.
    """
    def axis_size(name) -> int:
        names = name if isinstance(name, tuple) else (name,)
        n = 1
        for a in names:
            n *= mesh.shape.get(a, 1)
        return n

    def fix(leaf, spec):
        parts = tuple(spec) + (None,) * (leaf.ndim - len(spec))
        return P(*(name if name is not None and leaf.shape[i] % axis_size(name) == 0
                   else None
                   for i, name in enumerate(parts)))

    return jax.tree.map(fix, shapes_tree, specs_tree)


def named_shardings(mesh, specs_tree: Any):
    """PartitionSpec tree -> NamedSharding tree (device_put placement)."""
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def serving_param_shardings(params_tree: Any, mesh, policy: str = "cascade",
                            tied_embed: bool = False):
    """NamedSharding tree that places serving params (arrays or SDS leaves)
    on ``mesh`` under ``policy`` — the placement ``ServeEngine`` serves
    from, and the ``out_shardings`` that initialize params already sharded."""
    specs = filter_divisible(
        param_specs(params_tree, policy, tied_embed=tied_embed), params_tree, mesh)
    return named_shardings(mesh, specs)


def batch_specs(batch_tree: Any, batch_axes=("pod", "data"), mesh=None):
    """Shard the leading batch dim of every input over the data axes (and the
    M-RoPE position stream's axis 1). Falls back to replication when the
    batch doesn't divide the axes (long_500k has batch 1)."""
    sizes = 1
    if mesh is not None:
        for a in batch_axes:
            if a in mesh.shape:
                sizes *= mesh.shape[a]
    axes = tuple(a for a in batch_axes if mesh is None or a in mesh.shape)

    def spec(path, leaf):
        names = _path_names(path)
        bdim = 1 if names and names[-1] == "positions" else 0  # (3, B, S)
        if leaf.shape[bdim] % max(sizes, 1) != 0:
            return P(*(None,) * leaf.ndim)
        out = [None] * leaf.ndim
        out[bdim] = axes if len(axes) > 1 else (axes[0] if axes else None)
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, batch_tree)


def cache_specs(cache_tree: Any, mesh, model_axis: str = "model",
                batch_axes=("pod", "data")):
    """KV/state cache sharding: batch over data axes; heads (or head-like
    dims) over model where divisible. Cache layouts per family:
      attn   k/v: (L, B, T, Hkv, hd)     -> (None, data, None, model?, None)
      mla    c_kv: (L, B, T, lora)       -> (None, data, None, None)
      ssm    state: (L, B, H, P, N)      -> (None, data, model?, None, None)
      conv   (L, B, w-1, dim)            -> (None, data, None, model?)
    """
    model_size = mesh.shape.get(model_axis, 1)
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    data_size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    baxis = axes if len(axes) > 1 else (axes[0] if axes else None)

    def spec(path, leaf):
        names = _path_names(path)
        leaf_name = names[-1]
        # find batch dim: caches under stacked layers have shape (L, B, ...);
        # tail/dense (unstacked) have (B, ...)
        stacked = any(n in ("layers", "groups") for n in names)
        if leaf.ndim == 0 or leaf_name in ("pos", "slot_pos"):
            return P(*(None,) * leaf.ndim)
        bdim = 1 if stacked else 0
        out = [None] * leaf.ndim
        if leaf.shape[bdim] % max(data_size, 1) == 0 and data_size > 1:
            out[bdim] = baxis
        # shard a head-like dim over model when divisible; else shard the
        # cache TIME dim (sequence-sharded KV: decode attention contracts
        # over T per-shard and psums a tiny (B,H,1,dv) result — trades a
        # micro-collective for model_size-x less cache traffic/memory)
        if leaf_name in ("k", "v"):
            hdim = leaf.ndim - 2
            tdim = leaf.ndim - 3
            if leaf.shape[hdim] % model_size == 0:
                out[hdim] = model_axis
            elif leaf.shape[tdim] % model_size == 0:
                out[tdim] = model_axis
        elif leaf_name == "c_kv" and leaf.shape[-2] % model_size == 0:
            out[-2] = model_axis          # MLA latent cache: (L, B, T, lora)
        elif leaf_name == "k_rope" and leaf.shape[-2] % model_size == 0:
            out[-2] = model_axis
        elif leaf_name == "state" and leaf.ndim >= bdim + 4:
            hdim = bdim + 1
            if leaf.shape[hdim] % model_size == 0:
                out[hdim] = model_axis
        elif leaf_name in ("conv", "h"):
            if leaf.shape[-1] % model_size == 0:
                out[-1] = model_axis
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def add_data_dim(specs_tree, shapes_tree, mesh, batch_axes=("pod", "data")):
    """ZeRO-style: additionally shard each leaf over the data axes on its
    first unsharded, divisible dim. Applied to optimizer moments (ZeRO-1)
    and optionally to the params themselves (FSDP / ZeRO-3)."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    daxis = axes if len(axes) > 1 else (axes[0] if axes else None)

    def upd(spec, leaf):
        if size <= 1 or leaf.ndim == 0:
            return spec
        parts = list(spec) + [None] * (leaf.ndim - len(spec))
        for i in range(leaf.ndim):
            if parts[i] is None and leaf.shape[i] % size == 0:
                parts[i] = daxis
                return P(*parts)
        return spec

    return jax.tree.map(upd, specs_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation sharding discipline (set by launchers; no-op on bare CPU tests)
# ---------------------------------------------------------------------------

_ACT_POLICY: dict | None = None


def set_activation_policy(mesh, policy: str = "cascade",
                          batch_axes=("pod", "data"), seq_axis=None,
                          moe_ep: bool = False):
    """Install the activation-constraint discipline used inside model code.

    cascade:  residual stream (B, S, d) constrained to (batch, None, None) —
              features replicated over ``model`` so every matmul lowers to
              all-gather-of-activations + local contraction; NO partial-sum
              all-reduce can appear in a forward graph (the paper's CASCADE
              invariant, Section 13.5).
    seqpar:   residual constrained to (batch, model, None) — sequence
              parallelism between blocks (Korthikanti et al.); gathers move
              S/model-sized shards and reductions become reduce-scatters.
    none:     leave GSPMD propagation alone (measured baseline).
    """
    global _ACT_POLICY
    if policy == "fulldp":  # pure data parallelism: batch over every axis
        batch_axes = ("pod", "data", "model")
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    baxis = axes if len(axes) > 1 else (axes[0] if axes else None)
    _ACT_POLICY = {"policy": policy, "batch": baxis,
                   "seq": "model" if policy == "seqpar" else None,
                   "mesh": mesh, "batch_axes": batch_axes, "moe_ep": moe_ep}


def get_activation_policy():
    return _ACT_POLICY


def clear_activation_policy():
    global _ACT_POLICY
    _ACT_POLICY = None


def constrain_matmul_input(x):
    """CASCADE discipline for every linear input: features fully replicated
    over ``model`` (the paper's activation *broadcast*, Section 13.4) so the
    contraction stays local and no partial-sum all-reduce is emitted.
    Active only under the 'cascade' activation policy."""
    if _ACT_POLICY is None or _ACT_POLICY["policy"] not in ("cascade", "fulldp"):
        return x
    if x.ndim < 2:
        return x
    spec = P(_ACT_POLICY["batch"], *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_attn_queries(x, seq_dim: int = 1):
    """Shard the attention *query-position* dim over ``model`` (active under
    any installed policy). Heads often don't divide the model axis (GQA kv=8
    on a 16-way axis); sharding q-positions keeps every contraction local —
    zero partial-sum all-reduce — at the cost of gathering K/V once per
    layer. This is the CASCADE-consistent attention layout: row-blocks of
    activations distributed, weights/columns local."""
    if _ACT_POLICY is None or _ACT_POLICY["policy"] in ("none", "fulldp"):
        return x
    if x.ndim <= seq_dim or x.shape[seq_dim] % 16 != 0:
        return x
    spec = [None] * x.ndim
    spec[0] = _ACT_POLICY["batch"]
    spec[seq_dim] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_expert_buffer(x):
    """Constrain an (E, C, d) MoE dispatch/expert buffer.

    Under ``cascade`` the buffer is REPLICATED (the paper's activation
    broadcast, Section 13.4): expert weights are column-sharded, so every
    expert contraction is local and the combine is gather-only. NOTE the
    train-path caveat: building this buffer from data-SHARDED train tokens
    still cross-shard-combines at the scatter — the documented MoE-dispatch
    exception to the zero-partial-sum claim (an E-sharded buffer constraint
    did not avoid it either, see models/moe_shardmap.py, which exists to
    kill it; it would also now conflict with the column-sharded weights).
    Serving paths replicate the tokens BEFORE the scatter (moe_ffn_apply
    no_drop), so the invariant holds exactly where it is asserted. Other
    policies keep expert parallelism (E over ``model``): the scatter from
    data-sharded tokens then lowers to an all-to-all (tokens move once)
    instead of an all-reduce of the whole buffer across data shards."""
    if _ACT_POLICY is None or _ACT_POLICY["policy"] == "none":
        return x
    if _ACT_POLICY["policy"] in ("cascade", "fulldp"):
        return constrain_replicated(x)
    if x.ndim != 3 or x.shape[0] % 16 != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P("model", None, None))


def constrain_replicated(x):
    """Fully replicate an activation under the cascade policy (the CASCADE
    activation broadcast): inputs to contractions that do NOT go through
    ``cascade.linear_apply`` — attention q/k/v against a cache, the MoE
    dispatch scatter at serving batch sizes, and every logits row that
    feeds sampling (the decode row AND the speculative verify pass's full
    (B, K+1, V) row block: top-k / softmax / the Gumbel add / the
    rejection-resampling acceptance over a vocab-sharded row would all
    lower to partial-sum all-reduces) — are pinned replicated so no
    partial-sum all-reduce can be emitted downstream. No-op without an
    installed cascade/fulldp policy (CPU tests, megatron baseline)."""
    if _ACT_POLICY is None or _ACT_POLICY["policy"] not in ("cascade", "fulldp"):
        return x
    return jax.lax.with_sharding_constraint(x, P(*(None,) * x.ndim))


def constrain_residual(x):
    """Apply the installed activation constraint to a (B, S, d) residual."""
    if _ACT_POLICY is None or _ACT_POLICY["policy"] == "none":
        return x
    seq = _ACT_POLICY["seq"]
    if seq is not None and x.ndim >= 2 and x.shape[1] % 16 == 0:
        spec = P(_ACT_POLICY["batch"], seq, *(None,) * (x.ndim - 2))
    else:
        spec = P(_ACT_POLICY["batch"], *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, spec)
