"""Roofline-term extraction from compiled dry-run artifacts.

XLA's ``compiled.cost_analysis()`` and ``memory_analysis()`` on this backend
count while-loop (lax.scan) bodies ONCE and ignore loop-carried buffers —
verified empirically (a 50-iteration scan reports 1x body flops and misses
its carry).  Since the whole framework is scan-over-layers, we walk the
post-partitioning HLO text ourselves:

* computations are parsed into per-op symbol tables (name -> shape/dtype);
* every ``while`` contributes a trip-count multiplier, read from the
  ``s32[] constant(N)`` bound in its condition computation (lax.scan always
  lowers to such a bound); nested loops multiply;
* FLOPs: ``dot`` ops at 2 * result_elems * contraction_size * multiplier.
  Elementwise flops are not counted (documented; matmuls dominate every
  assigned arch, including decode matvecs);
* HBM traffic proxy: per op, result bytes + operand bytes (post-fusion HLO,
  so one op ~= one materialized buffer) * multiplier;
* collective wire bytes: ring-cost factors per op kind * multiplier;
* peak-memory estimate: entry arguments + the deepest chain of live
  while-carry tuples (remat stacks live there) + the largest single
  temporary.

Collective ring costs per chip (g = group size, B = per-device result):
  all-reduce 2B(g-1)/g; all-gather B(g-1)/g; reduce-scatter B(g-1);
  all-to-all B(g-1)/g; collective-permute B.
"""

from __future__ import annotations

import dataclasses
import math
import re
import warnings
from typing import Dict, List, Optional, Tuple

from repro.launch.mesh import (PEAK_FLOPS_BF16, HBM_BW, ICI_BW_PER_LINK,
                               ICI_LINKS_PER_RING)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "token": 0, "opaque": 0,
}

_COMP_HDR = re.compile(r"^(ENTRY\s+)?(%[\w\.\-]+)\s*\(.*\)\s*->")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*(.*)$")
_TYPE_RE = re.compile(r"^([a-z0-9]+)\[([\d,]*)\]")
_TUPLE_TYPE_RE = re.compile(r"^\(")
# an array type with its optional layout; TPU layouts carry tiling
# ("{1,0:T(8,128)(2,1)S(1)}"), CPU layouts only the dim order ("{1,0}")
_ARRAY_TYPE_RE = re.compile(r"^[a-z0-9]+\[[\d,]*\](?:\{[^{}]*\})?")
_KIND_RE = re.compile(r"^\s+([\w\-]+)\(")
_SHAPE_IN_TUPLE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_WHILE_RE = re.compile(r"condition=(%[\w\.\-]+),\s*body=(%[\w\.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=(%[\w\.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
# an operand token: optional inline type annotation + %name.  Newer XLA
# prints operands with their types ("dot(f32[128,256]{1,0} %Arg_0.1, ...)"),
# older HLO prints bare names ("dot(%p, %q)") — both must parse.
_OPERAND_TOKEN = re.compile(
    r"(?:([a-z0-9]+\[[\d,]*\])(?:\{[\d,]*\})?\s+)?(%[\w\.\-]+)")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _shape_bytes_from_type(tstr: str) -> int:
    """Bytes of a type string: 'bf16[2,3]{...}' or '(f32[2], s32[])'."""
    total = 0
    for m in _SHAPE_IN_TUPLE.finditer(tstr):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def _elems(shape: str) -> int:
    n = 1
    for d in shape.split(","):
        if d:
            n *= int(d)
    return n


@dataclasses.dataclass
class _Op:
    name: str
    kind: str
    type_str: str
    line: str


def _operands(op: _Op) -> List[Tuple[str, str]]:
    """Parse an op's operand list into (name, inline_type) pairs; the inline
    type is "" on older HLO that prints bare %names.  The argument group is
    found by matching the parenthesis after the op kind (depth-counted:
    tuple-typed operands contain nested parens)."""
    i = op.line.find(op.kind + "(")
    if i < 0:
        return []
    j = i + len(op.kind) + 1
    depth, k = 1, j
    while k < len(op.line) and depth:
        ch = op.line[k]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        k += 1
    inner = op.line[j:k - 1]
    return [(m.group(2), m.group(1) or "")
            for m in _OPERAND_TOKEN.finditer(inner)]


def _operand_type(table: Dict[str, str], name: str, inline: str) -> str:
    """Operand type string: the defining op's full type when the operand is
    defined in this computation, else the inline annotation."""
    return table.get(name) or inline


def _dot_contraction_size(op: _Op, table: Dict[str, str]) -> int:
    """Contraction size K of a ``dot``: product of the lhs dims named by
    ``lhs_contracting_dims``.  A silent failure here used to leave K = 1 and
    under-count 2*M*N*K as 2*M*N, so any unparsable piece now *warns loudly*
    (flops remain a lower bound) instead of passing as exact."""
    cm = _CONTRACT_RE.search(op.line)
    opnds = _operands(op)
    problem = None
    if not cm:
        problem = "no lhs_contracting_dims attribute"
    elif not opnds:
        problem = "could not parse operand list"
    else:
        lhs_name, lhs_inline = opnds[0]
        lhs_t = _operand_type(table, lhs_name, lhs_inline)
        lm = _TYPE_RE.match(lhs_t)
        if not lm:
            problem = f"no type found for lhs operand {lhs_name!r}"
        else:
            dims = lm.group(2).split(",")
            csize = 1
            try:
                for ci in cm.group(1).split(","):
                    if ci:
                        csize *= int(dims[int(ci)])
            except (IndexError, ValueError):
                problem = (f"contracting dims {cm.group(1)!r} out of range "
                           f"for lhs shape {lhs_t!r}")
            else:
                return csize
    warnings.warn(
        f"hlo_analysis: cannot determine dot contraction size "
        f"({problem}); FLOPs will be UNDER-counted for: {op.line.strip()}",
        stacklevel=2)
    return 1


def _type_and_kind(rhs: str) -> Optional[Tuple[str, str]]:
    """``(type string, op kind)`` of a definition's right-hand side
    (``"bf16[2,3]{1,0} dot(...)"`` or a tuple type ``"(f32[2], s32[])
    tuple(...)"``, whose members' layouts may hold parentheses); None
    when the line is not an op definition."""
    if rhs.startswith("("):
        depth = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        tstr, rest = rhs[:end + 1], rhs[end + 1:]
    else:
        tm = _ARRAY_TYPE_RE.match(rhs)
        if tm is None:
            return None
        tstr, rest = tm.group(0), rhs[tm.end():]
    km = _KIND_RE.match(rest)
    return (tstr, km.group(1)) if km else None


def parse_computations(hlo: str) -> Dict[str, List[_Op]]:
    comps: Dict[str, List[_Op]] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = _COMP_HDR.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            elif line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        name, rhs = dm.group(1), dm.group(2)
        parsed = _type_and_kind(rhs)
        if parsed is None:
            continue
        comps[cur].append(_Op(name, parsed[1], parsed[0], line))
    comps["__entry__"] = comps.get(entry, [])
    comps["__entry_name__"] = entry  # type: ignore
    return comps


def _trip_count(cond_ops: List[_Op]) -> int:
    best = 1
    for op in cond_ops:
        for m in _CONST_RE.finditer(op.line):
            best = max(best, int(m.group(1)))
    return best


def comp_multipliers(comps: Dict[str, List[_Op]]) -> Dict[str, float]:
    """Execution-count multiplier per computation (entry = 1)."""
    entry = comps.get("__entry_name__")
    mult: Dict[str, float] = {entry: 1.0} if entry else {}
    order = [entry] if entry else []
    seen = set(order)
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        m = mult.get(cname, 0.0)
        for op in comps.get(cname, []):
            if op.kind == "while":
                wm = _WHILE_RE.search(op.line)
                if not wm:
                    continue
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                mult[body] = mult.get(body, 0.0) + m * trips
                mult[cond] = mult.get(cond, 0.0) + m * (trips + 1)
                for c in (body, cond):
                    if c not in seen:
                        seen.add(c)
                        order.append(c)
            else:
                cm = _CALLS_RE.search(op.line)
                if cm:
                    callee = cm.group(1)
                    mult[callee] = mult.get(callee, 0.0) + m
                    if callee not in seen:
                        seen.add(callee)
                        order.append(callee)
    return mult


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len([e for e in m.group(1).split(",") if e.strip() != ""])
    return 1


@dataclasses.dataclass
class CollectiveStats:
    op: str
    count: float = 0
    result_bytes: float = 0    # per-device result bytes (x executions)
    wire_bytes: float = 0.0    # per-chip ring-model traffic (x executions)


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    wire_bytes: float = 0.0
    dot_count: float = 0.0
    collectives: Dict[str, CollectiveStats] = dataclasses.field(
        default_factory=dict)
    peak_bytes_est: float = 0.0


_SKIP_TRAFFIC = {"parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast", "while", "conditional", "after-all", "iota",
                 "partition-id", "replica-id"}


def analyze_hlo_text(hlo: str, argument_bytes: int = 0) -> HloCost:
    comps = parse_computations(hlo)
    mult = comp_multipliers(comps)
    cost = HloCost()

    # symbol tables: computation -> {op name -> type string}
    symtab: Dict[str, Dict[str, str]] = {}
    for cname, ops in comps.items():
        if cname.startswith("__"):
            continue
        symtab[cname] = {op.name: op.type_str for op in ops}

    def _param_types(cname: str) -> List[str]:
        return [op.type_str for op in comps.get(cname, [])
                if op.kind == "parameter"]

    def _dus_update_bytes(cname: str) -> Optional[float]:
        """If computation ``cname`` is rooted in a dynamic-update-slice
        (modulo bitcast/convert), return the update operand's bytes."""
        ops = comps.get(cname, [])
        table = symtab.get(cname, {})
        for op in ops:
            if op.kind == "dynamic-update-slice":
                opnds = _operands(op)
                if len(opnds) >= 2:
                    return float(_shape_bytes_from_type(
                        _operand_type(table, *opnds[1])))
        return None

    def _fusion_read_bytes(cname: str, operand_types: List[str]) -> float:
        """Effective read traffic of a fusion: a parameter consumed ONLY by
        dynamic-slice/gather ops inside the fusion is read at slice size,
        not full size (XLA emits the slice loads directly)."""
        ops = comps.get(cname, [])
        params = [op for op in ops if op.kind == "parameter"]
        # map parameter order to operand types (same order by construction)
        reads = 0.0
        for idx, pop in enumerate(params):
            full = _shape_bytes_from_type(
                operand_types[idx] if idx < len(operand_types)
                else pop.type_str)
            slice_bytes = 0.0
            sliced_only = True
            used = False
            for op in ops:
                if op.kind == "parameter":
                    continue
                names = [n for n, _ in _operands(op)]
                if pop.name not in names:
                    continue
                used = True
                if op.kind in ("dynamic-slice", "gather"):
                    slice_bytes += _shape_bytes_from_type(op.type_str)
                elif op.kind == "dynamic-update-slice" and \
                        names and names[0] == pop.name:
                    pass  # aliased in-place destination: no read
                else:
                    sliced_only = False
                    break
            if not used:
                continue
            reads += slice_bytes if sliced_only else full
        return reads

    while_tree: Dict[str, List[Tuple[str, float]]] = {}  # comp -> [(body, bytes)]
    largest_tmp = 0.0

    for cname, ops in comps.items():
        if cname.startswith("__"):
            continue
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        table = symtab[cname]
        for op in ops:
            tbytes = _shape_bytes_from_type(op.type_str)
            if op.kind == "dot":
                tm = _TYPE_RE.match(op.type_str)
                if tm:
                    res_elems = _elems(tm.group(2))
                    csize = _dot_contraction_size(op, table)
                    cost.flops += 2.0 * res_elems * csize * m
                    cost.dot_count += m
            if op.kind in COLLECTIVE_OPS or any(
                    op.kind == c + "-start" for c in COLLECTIVE_OPS):
                kind = op.kind.replace("-start", "")
                g = _group_size(op.line)
                b = tbytes
                if kind == "all-reduce":
                    wire = 2 * b * (g - 1) / max(g, 1)
                elif kind == "all-gather":
                    wire = b * (g - 1) / max(g, 1)
                elif kind == "reduce-scatter":
                    wire = b * (g - 1)
                elif kind == "all-to-all":
                    wire = b * (g - 1) / max(g, 1)
                else:
                    wire = b
                st = cost.collectives.setdefault(kind, CollectiveStats(kind))
                st.count += m
                st.result_bytes += b * m
                st.wire_bytes += wire * m
                cost.wire_bytes += wire * m
            if op.kind == "while":
                wm = _WHILE_RE.search(op.line)
                if wm:
                    while_tree.setdefault(cname, []).append(
                        (wm.group(2), tbytes))
            if op.kind not in _SKIP_TRAFFIC:
                # dynamic-slice/gather read only the slice, not the operand
                if op.kind in ("dynamic-slice", "gather"):
                    cost.hbm_bytes += 2.0 * tbytes * m
                    largest_tmp = max(largest_tmp, tbytes)
                    continue
                # in-place dynamic-update-slice only touches the slice: XLA
                # aliases the buffer, so charge 2x the update bytes, not the
                # full tensor (fusions rooted in a DUS included).
                dus_update = None
                if op.kind == "dynamic-update-slice":
                    opnds = _operands(op)
                    if len(opnds) >= 2:
                        dus_update = float(_shape_bytes_from_type(
                            _operand_type(table, *opnds[1])))
                elif op.kind == "fusion" and "dynamic-update-slice" in op.line:
                    cm = _CALLS_RE.search(op.line)
                    if cm:
                        dus_update = _dus_update_bytes(cm.group(1))
                if dus_update is not None:
                    cost.hbm_bytes += 2.0 * dus_update * m
                    continue
                operand_types = [_operand_type(table, nm, it)
                                 for nm, it in _operands(op)]
                if op.kind == "fusion":
                    cm = _CALLS_RE.search(op.line)
                    if cm and cm.group(1) in comps:
                        reads = _fusion_read_bytes(cm.group(1), operand_types)
                    else:
                        reads = sum(_shape_bytes_from_type(t)
                                    for t in operand_types)
                else:
                    reads = sum(_shape_bytes_from_type(t)
                                for t in operand_types)
                cost.hbm_bytes += (tbytes + reads) * m
                largest_tmp = max(largest_tmp, tbytes)

    # Peak estimate: arguments + the LARGEST single while-carry tuple + the
    # largest temporary.  Chaining nested tuples double-counts: inner-loop
    # carries and xs stacks alias slices of the outer carry (donated
    # arguments alias the param/opt stacks), so max() is the honest bracket
    # upper bound next to XLA's (loop-blind) lower bound.
    max_tuple = 0.0

    def walk(comp: str, seen) -> None:
        nonlocal max_tuple
        if comp in seen:
            return
        seen.add(comp)
        for body, b in while_tree.get(comp, []):
            max_tuple = max(max_tuple, b)
            walk(body, seen)

    entry = comps.get("__entry_name__")
    walk(entry, set())
    # donated arguments alias the training-state loop carry, so args and the
    # carry tuple are the SAME buffers: take the max, plus one transient.
    cost.peak_bytes_est = max(argument_bytes, max_tuple) + largest_tmp
    return cost


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    hbm_bytes_per_dev: float
    wire_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    collectives: Dict[str, Dict]
    model_flops_per_dev: float = 0.0
    peak_bytes_est: float = 0.0

    @property
    def useful_flops_ratio(self) -> float:
        return (self.model_flops_per_dev / self.flops_per_dev
                if self.flops_per_dev else 0.0)

    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """Useful-compute time over the dominant-term bound: how close the
        step is to the hardware roofline if it ran exactly at the bound."""
        t = self.bound_time()
        return (self.model_flops_per_dev / PEAK_FLOPS_BF16) / t if t else 0.0


def analyze(compiled, model_flops_total: float = 0.0, n_chips: int = 256
            ) -> Roofline:
    ma = compiled.memory_analysis()
    cost = analyze_hlo_text(compiled.as_text(),
                            argument_bytes=ma.argument_size_in_bytes)
    compute_s = cost.flops / PEAK_FLOPS_BF16
    memory_s = cost.hbm_bytes / HBM_BW
    coll_s = cost.wire_bytes / (ICI_BW_PER_LINK * ICI_LINKS_PER_RING)
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dom = max(terms, key=terms.get)
    return Roofline(
        flops_per_dev=cost.flops, hbm_bytes_per_dev=cost.hbm_bytes,
        wire_bytes_per_dev=cost.wire_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        dominant=dom,
        collectives={k: dataclasses.asdict(v)
                     for k, v in cost.collectives.items()},
        model_flops_per_dev=model_flops_total / max(n_chips, 1),
        peak_bytes_est=cost.peak_bytes_est,
    )


def parse_collectives(hlo_text: str) -> Dict[str, CollectiveStats]:
    """Collective stats with trip-count multipliers (public helper)."""
    return analyze_hlo_text(hlo_text).collectives


# ----------------------------------------------- HLO -> transfer specs ----
#
# The planner's config-level ``step_transfer_specs`` are *estimates*; the
# compiled step's HLO is ground truth for what actually moves.  Each
# collective lowering maps onto one of the paper's transfer archetypes:
#
#   all-to-all          -> "moe_dispatch":  every shard exchanges distinct
#                          b/g-byte chunks with its g-1 peers — per-pair
#                          unicast writes (the 1-destination multicast
#                          degeneracy), priced at fan-out 1;
#   collective-permute  -> "stage_activation": the next stage pulls its
#                          predecessor's output — read-channel P2P;
#   all-gather          -> "weights": each shard broadcasts its b/g-byte
#                          shard to the g-1 peers — the multicast archetype;
#   all-reduce          -> "grad_reduce", reduce-scatter -> "grad_scatter":
#                          reductions; the NoC forks multicast flits but
#                          cannot combine in flight, so these are marked
#                          ``reduce`` and the planner pins them to MEM.
#
# Specs are emitted *per layer*: a collective op inside the
# scan-over-layers while body executes once per layer (its trip-count
# multiplier), and each execution is one transfer, named
# ``"<archetype>.L<index>"`` with layer indices assigned in module parse
# order across the archetype's op instances (scanned groups expand to their
# trip count; unscanned remainder layers are their own instances).  Config
# estimates are kept only for logical transfers the HLO does not exhibit.

_HLO_SPEC_ARCHETYPES = {
    "all-to-all": "moe_dispatch",
    "collective-permute": "stage_activation",
    "all-gather": "weights",
    "all-reduce": "grad_reduce",
    "reduce-scatter": "grad_scatter",
}

# Per-layer expansion bound: a collective under a non-layer loop (e.g. a
# long chunk scan) can carry a huge multiplier; past this many layers the
# archetype degrades to the single dominant-op spec instead of flooding the
# planner with identical rows.  Sized above the all-reduce census of a
# 40-layer training step (dbrx train_4k executes 137: the gradient
# reductions of three scanned regions plus optimizer-side reductions), so
# real per-layer gradient traffic prices layer by layer — collapsing it to
# one dominant spec with mult=137 charges every execution at the dominant
# byte count and buries the overlap fraction.
_PER_LAYER_CAP = 160

_SPEC_CACHE: Dict[str, Dict[str, List]] = {}


def _collective_result_bytes(tstr: str) -> int:
    """Result-buffer bytes of a collective's type string.  Async ``-start``
    ops are tuple-typed ``(operand, result[, context])`` — summing the whole
    tuple would over-count the transfer (e.g. (g+1)/g x for an all-gather),
    so take the largest member: the gathered/permuted result."""
    if not tstr.lstrip().startswith("("):
        return _shape_bytes_from_type(tstr)
    best = 0
    for m in _SHAPE_IN_TUPLE.finditer(tstr):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES.get(m.group(1), 4))
    return best


def _comp_dot_flops(comps: Dict[str, List[_Op]]) -> Dict[str, float]:
    """Per-*execution* dot FLOPs of each computation, including the
    computations it calls (``calls``/``to_apply`` — fusions hide the dots
    one level down) but NOT its while loops (loop compute is not adjacent
    to a single collective execution) and NOT the ``to_apply`` of a
    collective op (a reduction's combiner is the wire-side add, not
    producer/consumer compute the transfer can hide behind).  This is the
    "compute a collective feeds" term of the overlap objective: a
    collective lowered into a computation overlaps the matmuls that
    computation runs."""
    direct: Dict[str, float] = {}
    callees: Dict[str, List[str]] = {}
    for cname, ops in comps.items():
        if cname.startswith("__"):
            continue
        table = {op.name: op.type_str for op in ops}
        f = 0.0
        calls: List[str] = []
        for op in ops:
            if op.kind == "dot":
                tm = _TYPE_RE.match(op.type_str)
                if tm:
                    f += 2.0 * _elems(tm.group(2)) * \
                        _dot_contraction_size(op, table)
            elif op.kind != "while" and \
                    op.kind.replace("-start", "") not in COLLECTIVE_OPS:
                cm = _CALLS_RE.search(op.line)
                if cm:
                    calls.append(cm.group(1))
        direct[cname] = f
        callees[cname] = calls

    closed: Dict[str, float] = {}

    def total(cname: str, stack: Tuple[str, ...] = ()) -> float:
        if cname in closed:
            return closed[cname]
        if cname in stack:   # defensive: HLO call graphs are acyclic
            return 0.0
        f = direct.get(cname, 0.0) + sum(
            total(c, stack + (cname,)) for c in callees.get(cname, ()))
        closed[cname] = f
        return f

    for cname in direct:
        total(cname)
    return closed


def collective_op_details(hlo: str) -> List[Dict]:
    """One entry per collective op in the module: kind, per-execution
    result bytes, group size, the trip-count multiplier of its
    computation, the computation name (``comp``) — ops sharing a
    computation execute together (one layer of a scanned stack) — and the
    computation's per-execution dot FLOPs (``dot_flops``, the consumer
    compute the overlap objective hides the transfer behind)."""
    comps = parse_computations(hlo)
    mult = comp_multipliers(comps)
    dot_flops = _comp_dot_flops(comps)
    out: List[Dict] = []
    for cname, ops in comps.items():
        if cname.startswith("__"):
            continue
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for op in ops:
            kind = op.kind.replace("-start", "")
            if kind not in COLLECTIVE_OPS or (
                    op.kind != kind and op.kind != kind + "-start"):
                continue
            out.append({
                "kind": kind,
                "bytes": _collective_result_bytes(op.type_str),
                "group": _group_size(op.line),
                "mult": m,
                "comp": cname,
                "dot_flops": dot_flops.get(cname, 0.0),
            })
    return out


def _spec_from_detail(kind: str, name: str, det: Dict, layer=None, mult=1):
    """One TransferSpec from a collective op's (bytes, group) per the
    archetype table above.  ``mult`` > 1 marks a capped dominant spec
    standing for that many layer executions.

    The computation's dot FLOPs ride along as ``compute_flops`` — the
    adjacent compute the overlap objective hides the transfer behind.
    ``all-reduce`` carries it too: the combine itself still cannot ride
    the NoC (the reduce pin in the planner holds), but the C5 IDMA/CDMA
    decoupling lets the memory-path round-trip stream behind the producer
    matmuls of the same computation (``PlanDecision.streamed``), and the
    fused ring reduce-scatter remains a candidate when the chain beats
    the round-trip outright."""
    from repro.core.planner import TransferSpec

    g = max(det["group"], 1)
    b = int(det["bytes"])
    flops = float(det.get("dot_flops", 0.0))
    if kind == "all-to-all":
        return TransferSpec(name, nbytes=max(b // g, 1), fan_out=1,
                            layer=layer, mult=mult, compute_flops=flops)
    if kind == "collective-permute":
        return TransferSpec(name, nbytes=max(b, 1), fan_out=1, pull=True,
                            layer=layer, mult=mult, compute_flops=flops)
    if kind == "all-gather":
        return TransferSpec(name, nbytes=max(b // g, 1),
                            fan_out=max(g - 1, 1), layer=layer, mult=mult,
                            compute_flops=flops)
    if kind == "all-reduce":
        return TransferSpec(name, nbytes=max(b, 1), fan_out=max(g - 1, 1),
                            reduce=True, layer=layer, mult=mult,
                            compute_flops=flops)
    # reduce-scatter: the fused ring kernel's combine-at-every-hop makes
    # this the canonical FUSED_RING producer-side transfer
    return TransferSpec(name, nbytes=max(b // g, 1),
                        fan_out=max(g - 1, 1), reduce=True, layer=layer,
                        mult=mult, compute_flops=flops)


def transfer_specs_from_hlo(hlo_text: str, fallback=None):
    """Derive planner :class:`~repro.core.planner.TransferSpec`s from the
    compiled step's collective ops (see the archetype table above), one
    spec per layer per archetype.

    Same-kind ops within one computation execute together — they are the
    distinct tensors of ONE layer of a scanned stack (e.g. each weight
    matrix's all-gather) — so they aggregate into a single per-layer
    transfer (bytes summed, group size from the largest op).  The
    aggregate then expands by the computation's trip-count multiplier
    ``m`` into ``m`` layer-specs (``"weights.L0"`` ...
    ``"weights.L<m-1>"``); computations number consecutively in parse
    order, so names are stable for a given module.  An archetype exhibited
    by exactly one execution keeps its bare name (``"weights"``).
    ``fallback`` (the config-level spec list) fills in logical transfers
    absent from the HLO and fixes the output order — a fallback entry
    whose archetype the HLO exhibits is replaced by that archetype's
    per-layer specs in place.  Parsed results are cached by module digest
    so repeated pricing per launch is free.
    """
    import hashlib

    digest = hashlib.sha1(hlo_text.encode()).hexdigest()
    derived = _SPEC_CACHE.get(digest)
    if derived is None:
        # (kind, computation) -> one aggregated per-execution transfer
        agg: Dict[Tuple[str, str], Dict] = {}
        for det in collective_op_details(hlo_text):
            key = (det["kind"], det["comp"])
            cur = agg.get(key)
            if cur is None:
                agg[key] = dict(det, dom_bytes=det["bytes"])
            else:
                cur["bytes"] += det["bytes"]
                if det["bytes"] > cur["dom_bytes"]:
                    cur["dom_bytes"] = det["bytes"]
                    cur["group"] = det["group"]
        # a computation's dot FLOPs are ONE pool of adjacent compute
        # shared by all its collectives: apportion it across the
        # compute-bearing aggregates so the serial objective charges the
        # compute once per computation (not once per transfer) and the
        # overlap objective cannot hide every transfer behind the same
        # matmul simultaneously.  The split is weighted by each
        # aggregate's wire bytes — a transfer's DMA spans a window of the
        # surrounding compute proportional to its payload, so the big
        # gradient reduction gets the wide backward-matmul window while a
        # small dispatch gets the sliver it actually needs; an even split
        # would strand most of the pool on transfers whose comm is already
        # far smaller than their share.  All-reduce aggregates share too:
        # the combine itself stays wire-side (see ``_spec_from_detail``),
        # but the C5 streamed memory path hides the round-trip behind the
        # producer matmuls of the same computation.
        sharers: Dict[str, List[Dict]] = {}
        for (kind, comp), a in agg.items():
            if a.get("dot_flops", 0.0) > 0:
                sharers.setdefault(comp, []).append(a)
        for items in sharers.values():
            total_bytes = sum(max(a["bytes"], 1) for a in items)
            for a in items:
                a["dot_flops"] = (a["dot_flops"] *
                                  max(a["bytes"], 1) / total_bytes)
        per_kind: Dict[str, List[Dict]] = {}
        for (kind, _), a in agg.items():
            per_kind.setdefault(kind, []).append(a)
        derived = {}
        for kind, name in _HLO_SPEC_ARCHETYPES.items():
            dets = per_kind.get(kind)
            if not dets:
                continue
            layers: List[Dict] = []
            for det in dets:
                layers.extend([det] * max(int(round(det["mult"])), 1))
            if len(layers) == 1:
                derived[name] = [_spec_from_detail(kind, name, layers[0])]
            elif len(layers) > _PER_LAYER_CAP:
                # degrade to the dominant per-layer transfer but keep the
                # execution count: step-cost totals stay continuous across
                # the cap instead of collapsing to one execution
                dom = max(dets, key=lambda d: d["bytes"])
                derived[name] = [_spec_from_detail(kind, name, dom,
                                                   mult=len(layers))]
            else:
                derived[name] = [
                    _spec_from_detail(kind, f"{name}.L{i}", det, layer=i)
                    for i, det in enumerate(layers)]
        _SPEC_CACHE[digest] = derived
    out, taken = [], set()
    for s in fallback or ():
        group = derived.get(s.name)
        if group is not None:
            out.extend(group)
            taken.add(s.name)
        else:
            out.append(s)
    for base in sorted(set(derived) - taken):
        out.extend(derived[base])
    return out
