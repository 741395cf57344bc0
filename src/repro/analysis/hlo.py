"""Structural HLO analysis: the compiled-program half of the serving
contract (moved here from ``benchmarks/hlo_analysis.py``, which remains as
an import shim).

``compiled.cost_analysis()`` counts a while-loop body ONCE (verified
empirically), which would undercount scanned-layer models by n_layers. This
module parses ``compiled.as_text()`` into a computation call graph, reads
``known_trip_count`` off every while op, and propagates multiplicities to:

* dot FLOPs (2 * prod(out_shape) * prod(contracted lhs dims)), and
* collective bytes (output tensor bytes per op, per device),

giving loop-corrected per-device totals. Convolution/elementwise FLOPs are
ignored (dots dominate every assigned arch).

On top of the parser sit the per-step invariant checkers that
``analysis/contract.py`` applies to every jitted serving closure:

* ``donation_aliases``   — which params the compiler actually aliased to
                           outputs (``input_output_alias``); a silently
                           dropped cache donation doubles KV HBM.
* ``host_transfers``     — infeed/outfeed/send/recv and host-callback
                           custom-calls; a serving step must be one pure
                           device dispatch.
* ``dtype_audit``        — per-dtype dot census, forbidden-dtype hits, and
                           the packed-vs-float ENTRY parameter split (the
                           FP4 path's weights are u8 code planes, never
                           dense floats).
* ``collective_budget``  — loop-corrected per-kind collective counts and
                           bytes against a declared budget, generalizing
                           ``partial_sum_allreduces``.

Everything here is pure text analysis — no jax import, so the checkers run
on stored HLO dumps as well as live lowerings.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f4e2m1fn": 1, "s4": 1, "u4": 1,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# dtypes whose ENTRY parameters count as packed/code planes (the serve_fp4
# weight format stores two E2M1 nibbles per u8; scales ride as u8 E8M0)
_PACKED_DTYPES = ("u8", "s8", "u4", "s4", "f8e4m3fn", "f8e5m2", "f8e4m3",
                  "f4e2m1fn")
_FLOAT_DTYPES = ("f16", "bf16", "f32", "f64")

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\((.*?)\)\s*->\s*(.+?)\s*\{\s*$")
_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")
_OP_RE = re.compile(r"\s+([\w\-]+)\((.*)$")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|to_apply|condition|body)=%?([\w\.\-]+)")
_OPERANDS_RE = re.compile(r"%([\w\.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*((?:\([^)]*\)|[\w\[\],\{\}:]+))")
# one alias entry on the HloModule header line:
#   {output_index}: (param_number, {param_index}, may-alias|must-alias)
# the kind literal disambiguates entries, so no balanced-brace scan needed
_ALIAS_RE = re.compile(
    r"\{\s*([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{\s*([\d,\s]*)\}\s*,?\s*"
    r"(may-alias|must-alias)?\s*\)")
_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')

# host ops by opcode; plus custom-call targets that round-trip to the host
# (python callbacks — io_callback/pure_callback/debug.callback lower to
# ``xla_python_cpu_callback`` variants — and host-memory offload moves)
_HOST_OPS = ("infeed", "outfeed", "send", "send-done", "recv", "recv-done")
_HOST_CC_MARKERS = ("callback", "host", "infeed", "outfeed")


def _shape_dims(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(type_str):
        if dt in _DTYPE_BYTES:
            total += math.prod(dims) * _DTYPE_BYTES[dt] if dims else _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str


@dataclasses.dataclass
class Computation:
    name: str
    params: Dict[str, str]
    instrs: List[Instr]


def _match_instr(raw: str) -> Optional[Tuple[str, str, str, str]]:
    """``(name, type, op, rest)`` of one instruction line, else None. The
    type is scanned, not matched: TPU layouts carry tiling annotations
    (``bf16[4,5120]{1,0:T(4,128)(2,1)S(1)}``), so a tuple type nests
    parentheses."""
    m = _INSTR_HEAD_RE.match(raw)
    if m is None:
        return None
    rest = raw[m.end():]
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = len(rest.split(None, 1)[0]) if rest.strip() else 0
    om = _OP_RE.match(rest, end)
    if not end or om is None:
        return None
    return m.group(1), rest[:end], om.group(1), om.group(2)


def parse_computations(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry = None
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        m = _COMP_RE.match(raw)
        if m:
            is_entry, name, params_str, _ = m.groups()
            params = {}
            for pm in _PARAM_RE.finditer(params_str):
                params[pm.group(1)] = pm.group(2)
            cur = Computation(name=name, params=params, instrs=[])
            comps[name] = cur
            if is_entry:
                entry = name
            continue
        if cur is None:
            continue
        if raw.strip() == "}":
            cur = None
            continue
        im = _match_instr(raw)
        if im:
            cur.instrs.append(Instr(*im))
    return comps, entry


def _multiplicities(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    """computation name -> times executed per program run."""
    mult: Dict[str, float] = defaultdict(float)

    def visit(name: str, m: float, stack=()):
        if name not in comps or name in stack:
            return
        mult[name] += m
        for ins in comps[name].instrs:
            trip = 1.0
            if ins.op == "while":
                tm = _TRIP_RE.search(ins.rest)
                trip = float(tm.group(1)) if tm else 1.0
            for kw in _CALL_RE.finditer(ins.rest):
                child_m = m
                if kw.group(0).startswith("body="):
                    child_m = m * trip
                elif kw.group(0).startswith("condition="):
                    child_m = m * (trip + 1)
                visit(kw.group(1), child_m, stack + (name,))

    visit(entry, 1.0)
    return dict(mult)


def analyze(text: str) -> dict:
    """Loop-corrected per-device dot FLOPs + collective bytes."""
    comps, entry = parse_computations(text)
    if entry is None:
        raise ValueError("no ENTRY computation found")
    mult = _multiplicities(comps, entry)

    dot_flops = 0.0
    dot_flops_uncorrected = 0.0
    coll = {c: {"count": 0.0, "bytes": 0.0, "bytes_uncorrected": 0.0} for c in _COLLECTIVES}

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        # symbol table: instruction/param name -> type string
        sym: Dict[str, str] = dict(comp.params)
        for ins in comp.instrs:
            sym[ins.name] = ins.type_str
        for ins in comp.instrs:
            if ins.op == "dot":
                out_dims = _shape_dims(ins.type_str)
                out_elems = math.prod(out_dims[0][1]) if out_dims and out_dims[0][1] else 1
                ops = _OPERANDS_RE.findall(ins.rest)
                cd = _CDIMS_RE.search(ins.rest)
                k = 1
                if ops and cd is not None and ops[0] in sym:
                    lhs_dims = _shape_dims(sym[ops[0]])
                    if lhs_dims and lhs_dims[0][1]:
                        for d in cd.group(1).split(","):
                            if d:
                                k *= lhs_dims[0][1][int(d)]
                f = 2.0 * out_elems * k
                dot_flops += m * f
                dot_flops_uncorrected += f
            else:
                base = None
                for c in _COLLECTIVES:
                    if ins.op == c or ins.op == c + "-start":
                        base = c
                        break
                if base is not None:
                    b = _type_bytes(ins.type_str)
                    coll[base]["count"] += m
                    coll[base]["bytes"] += m * b
                    coll[base]["bytes_uncorrected"] += b

    total_coll = sum(v["bytes"] for v in coll.values())
    return {
        "dot_flops": dot_flops,
        "dot_flops_uncorrected": dot_flops_uncorrected,
        "collectives": coll,
        "collective_bytes": total_coll,
    }


def partial_sum_allreduces(text: str) -> dict:
    """Count all-reduce ops whose combiner is an ADD — partial-sum traffic,
    the quantity CASCADE abolishes (paper Sections 2.2, 13.5).

    An all-reduce's reduction computation is named by ``to_apply=``; a
    combiner CONTAINING an ``add`` accumulates partial products (max/min/or
    combiners — argmax lowerings, mask folds — are not partial sums and are
    ignored). Containment rather than root-op equality matters for variadic
    all-reduces (XLA's combiner pass merges several into one op whose
    combiner ROOTs a ``tuple`` of adds), and the async ``-start`` forms of
    both all-reduce and reduce-scatter are counted — a gate must
    over-approximate, never false-negative. Returns
    ``{"count", "bytes", "ops": [(name, bytes), ...]}`` over EVERY
    computation in the module, loop bodies included — the serving assertion
    is "zero partial-sum all-reduce anywhere in the decode step", so no
    multiplicity weighting is needed.
    """
    comps, _ = parse_computations(text)
    out = {"count": 0, "bytes": 0, "ops": []}
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op not in ("all-reduce", "all-reduce-start",
                              "reduce-scatter", "reduce-scatter-start"):
                continue
            target = None
            for kw in _CALL_RE.finditer(ins.rest):
                if kw.group(0).startswith("to_apply="):
                    target = kw.group(1)
                    break
            combiner_adds = (target in comps and
                             any(i.op == "add" for i in comps[target].instrs))
            if combiner_adds:
                b = _type_bytes(ins.type_str)
                out["count"] += 1
                out["bytes"] += b
                out["ops"].append((f"{comp.name}/{ins.name}", b))
    return out


# ----------------------------------------------------------- new checkers
def donation_aliases(text: str) -> dict:
    """Parse ``input_output_alias`` off the HloModule header line.

    The compiler records every donation it HONORED as
    ``{output_index}: (param_number, {param_index}, may-alias)``; a donated
    buffer the compiler could not alias simply has no entry, so the contract
    check is "every donated cache leaf (above a size floor) has an alias
    entry" — a silently dropped donation means the step holds input AND
    output cache copies live, doubling KV HBM. Each entry carries the
    aliased ENTRY parameter's byte size (per-shard under a mesh), so the
    contract can ignore advisory-size leaves — e.g. a rewind step's tiny
    ``pos`` vector is legitimately recomputed from the checkpoint rather
    than aliased. Returns ``{"count", "bytes", "params", "aliases"}``.
    """
    head = ""
    for line in text.splitlines():
        if line.startswith("HloModule"):
            head = line
            break
    comps, entry = parse_computations(text)
    # ENTRY parameter byte sizes by position (params dict keeps order)
    param_bytes = ([_type_bytes(t) for t in comps[entry].params.values()]
                   if entry is not None else [])
    aliases = []
    if "input_output_alias" in head:
        # the alias attribute is the only place the (out, param, kind)
        # triple syntax appears, so matching entries on the whole header
        # line is safe despite the nested braces
        for m in _ALIAS_RE.finditer(head.split("input_output_alias=", 1)[1]):
            p = int(m.group(2))
            aliases.append({
                "output_index": tuple(int(x) for x in m.group(1).split(",")
                                      if x.strip()),
                "param": p,
                "param_index": tuple(int(x) for x in m.group(3).split(",")
                                     if x.strip()),
                "kind": m.group(4) or "may-alias",
                "bytes": param_bytes[p] if p < len(param_bytes) else 0,
            })
    return {
        "count": len(aliases),
        "bytes": sum(a["bytes"] for a in aliases),
        "params": sorted({a["param"] for a in aliases}),
        "aliases": aliases,
    }


def host_transfers(text: str) -> dict:
    """Host round-trips anywhere in the module: infeed/outfeed/send/recv
    opcodes plus custom-calls whose target names a python callback or host
    placement. A serving step closure must be ONE device dispatch — a host
    transfer inside it serializes every step on PCIe + the GIL. Returns
    ``{"count", "ops": [(comp/name, opcode-or-target), ...]}``.
    """
    comps, _ = parse_computations(text)
    out = {"count": 0, "ops": []}
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op in _HOST_OPS:
                out["ops"].append((f"{comp.name}/{ins.name}", ins.op))
            elif ins.op == "custom-call":
                m = _CC_TARGET_RE.search(ins.rest)
                tgt = m.group(1) if m else ""
                if any(k in tgt.lower() for k in _HOST_CC_MARKERS):
                    out["ops"].append((f"{comp.name}/{ins.name}", tgt))
    out["count"] = len(out["ops"])
    return out


def dtype_audit(text: str, forbid: Tuple[str, ...] = ("f64",)) -> dict:
    """Dtype census of a step: per-dtype dot counts, forbidden-dtype hits
    (any instruction whose output shape uses a forbidden dtype), and the
    ENTRY-parameter split into packed (u8/u4/fp8 code planes) vs dense
    float weights.

    The FP4-path contract is checked on the SIGNATURE, not the dot dtypes:
    packed serve_fp4 weights enter the step as u8 code+scale planes, while
    a silently densified tree enters as f32/bf16 — but interpret-mode
    Pallas kernels (the CPU-exact dequant paths) legitimately emit float
    dequant-dots inside the step, so "no f32 dot" would false-positive on
    every CPU run. ``contract.audit_engine`` therefore requires
    ``packed_params > 0`` on fused/FP4 steps and leaves dot dtypes as
    reported facts.
    """
    comps, entry = parse_computations(text)
    dot_dtypes: Dict[str, int] = defaultdict(int)
    forbidden: List[Tuple[str, str]] = []
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op == "dot":
                sd = _shape_dims(ins.type_str)
                if sd:
                    dot_dtypes[sd[0][0]] += 1
            for dt, _dims in _shape_dims(ins.type_str):
                if dt in forbid:
                    forbidden.append((f"{comp.name}/{ins.name}", dt))
    packed_params = float_params = 0
    packed_bytes = float_bytes = 0
    if entry is not None:
        for _pname, ptype in comps[entry].params.items():
            dts = {dt for dt, _ in _shape_dims(ptype)}
            b = _type_bytes(ptype)
            if dts & set(_PACKED_DTYPES):
                packed_params += 1
                packed_bytes += b
            elif dts & set(_FLOAT_DTYPES):
                float_params += 1
                float_bytes += b
    return {
        "dot_dtypes": dict(dot_dtypes),
        "forbidden": forbidden,
        "packed_params": packed_params,
        "float_params": float_params,
        "packed_param_bytes": packed_bytes,
        "float_param_bytes": float_bytes,
    }


def collective_budget(text: str, max_counts: Optional[Dict[str, float]] = None,
                      max_bytes: Optional[float] = None,
                      max_partial_sum: Optional[int] = 0) -> dict:
    """Check loop-corrected collective counts/bytes against a declared
    budget, generalizing the ``partial_sum_allreduces`` gate.

    ``max_counts`` caps the loop-corrected count per collective kind (keys
    from ``all-gather``/``all-reduce``/``reduce-scatter``/``all-to-all``/
    ``collective-permute``; missing keys are uncapped). ``max_bytes`` caps
    total loop-corrected collective bytes per step. ``max_partial_sum``
    caps add-combiner all-reduce/reduce-scatter ops (None = uncapped).
    Returns the measured facts plus ``violations`` —
    ``[(what, measured, budget), ...]``, empty when within budget.
    """
    facts = analyze(text)
    psum = partial_sum_allreduces(text)
    violations: List[Tuple[str, float, float]] = []
    for kind, cap in (max_counts or {}).items():
        got = facts["collectives"].get(kind, {}).get("count", 0.0)
        if got > cap:
            violations.append((f"{kind} count", got, float(cap)))
    if max_bytes is not None and facts["collective_bytes"] > max_bytes:
        violations.append(("collective bytes", facts["collective_bytes"],
                           float(max_bytes)))
    if max_partial_sum is not None and psum["count"] > max_partial_sum:
        violations.append(("partial-sum all-reduces", float(psum["count"]),
                           float(max_partial_sum)))
    return {
        "collectives": facts["collectives"],
        "collective_bytes": facts["collective_bytes"],
        "partial_sum": psum,
        "violations": violations,
    }
