"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only JAX's own reader (``jax.profiler.ProfileData``) is used.  A device is a
plane named ``/device:<KIND>:<n>`` (TPU, GPU); its operations are the events
of the line named ``XLA Ops``.  Host spans are the events of the host plane's
thread lines, among them the harness's ``jax.profiler.TraceAnnotation``
names.  Device and host events share one clock in the trace.

Everything here works on plain ``(start_ns, end_ns)`` intervals, so it is
checked on a small recorded trace without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# the collective HLO ops; async ones appear as a -start / -done pair and run
# from the start's beginning to the done's end
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast", "ragged-all-to-all")
_COLL_RE = re.compile(r"^(%s)(-start|-done)?(\.\d+)?$" % "|".join(
    re.escape(c) for c in COLLECTIVES))
# the opcode in an instruction's text: the first lowercase word before a
# "(" after the " = " (layouts in the type hold only upper-case tiles)
_OPCODE_RE = re.compile(r"(?<![\w.-])([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class DeviceOps:
    """One device's operations: ``ops`` as ``(name, start_ns, end_ns)``;
    ``kinds`` gives, by name, the ``collective_kind`` of each collective
    op, read from its opcode (JAX names a collective after its primitive:
    an all-reduce may be ``psum.7``, an all-to-all ``all_to_all.15``)."""
    name: str
    ops: List[Tuple[str, float, float]]
    kinds: Dict[str, Tuple[str, str]] = dataclasses.field(
        default_factory=dict)


@dataclasses.dataclass
class Trace:
    devices: List[DeviceOps]
    host_spans: List[Tuple[str, float, float]]


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler.start_trace(trace_dir)``
    session wrote."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """Read a trace.  ``host_names`` keeps only host spans of those names
    (the harness's annotations); None keeps every host span."""
    from jax.profiler import ProfileData
    keep = None if host_names is None else set(host_names)
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, kinds = [], {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    ops.append((name, float(e.start_ns), float(e.end_ns)))
                    kind = collective_kind(op_code(e.name))
                    if kind is not None:
                        kinds[name] = kind
            ops.sort(key=lambda o: o[1])
            devices.append(DeviceOps(plane.name, ops, kinds))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if keep is None or e.name in keep:
                        host.append((e.name, float(e.start_ns),
                                     float(e.end_ns)))
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(2)))
    host.sort(key=lambda s: s[1])
    return Trace(devices, host)


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each op event by the
    instruction's whole text (``%fusion.3 = bf16[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_code(event_name: str) -> str:
    """The HLO opcode in an op event's text (``all-reduce`` for ``%psum.7
    = bf16[512,1024]{1,0:T(8,128)(2,1)} all-reduce(...)``), or ``""``."""
    parts = event_name.split(" = ", 1)
    m = _OPCODE_RE.search(parts[1]) if len(parts) == 2 else None
    return m.group(1) if m else ""


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def collective_kind(op_name: str) -> Optional[Tuple[str, str]]:
    """``(kind, "" | "-start" | "-done")`` for a collective op name."""
    m = _COLL_RE.match(op_name)
    return (m.group(1), m.group(2) or "") if m else None


def split_ops(ops: Sequence[Tuple[str, float, float]],
              kinds: Optional[Dict[str, Tuple[str, str]]] = None
              ) -> Tuple[List[Interval], List[Interval]]:
    """(collective intervals, other intervals) of one device.  An op is a
    collective by ``kinds`` (its opcode) or else by its name.  An async
    collective spans from its ``-start`` to the matching ``-done`` (the
    earliest open start of the same kind)."""
    coll, other = [], []
    open_starts: Dict[str, List[float]] = {}
    kinds = kinds or {}
    for name, s, e in ops:
        ck = kinds.get(name) or collective_kind(name)
        if ck is None:
            other.append((s, e))
            continue
        kind, phase = ck
        if phase == "-start":
            open_starts.setdefault(kind, []).append(s)
        elif phase == "-done" and open_starts.get(kind):
            coll.append((open_starts[kind].pop(0), e))
        else:
            coll.append((s, e))
    for starts in open_starts.values():   # a start whose done fell outside
        coll.extend((s, s) for s in starts)
    return coll, other


def leaves(intervals: Iterable[Interval]) -> List[Interval]:
    """The intervals that hold no other.  A device runs one op at a time
    but for nesting: a ``while`` (or ``call``, ``conditional``) op spans the
    ops of its body, so an op that holds the op after it is a container."""
    ivs = sorted(intervals)
    return [(s, e) for i, (s, e) in enumerate(ivs)
            if not (i + 1 < len(ivs) and ivs[i + 1][0] < e
                    and ivs[i + 1][1] <= e and ivs[i + 1] != (s, e))]


def window_of(trace: Trace, annotation: Optional[str] = None) -> Interval:
    """The traced window: the host span named ``annotation`` if given,
    else from the first to the last device op."""
    if annotation is not None:
        spans = [(s, e) for n, s, e in trace.host_spans if n == annotation]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
    starts = [d.ops[0][1] for d in trace.devices if d.ops]
    ends = [max(o[2] for o in d.ops) for d in trace.devices if d.ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # mean over devices
    collective_exposed_s: float        # mean over devices
    top_ops: List[Tuple[str, float]]   # seconds per op name, device mean
    idle_by_host: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def collective_exposed_share(self) -> float:
        return self.collective_exposed_s / self.window_s


def reduce(trace: Trace, window: Interval, n_top: int = 10) -> Reduction:
    """Busy time (union of device-op intervals), collective-only time
    (collective intervals not covered by another op that holds no other:
    a loop around a collective does not hide it), the ops that took most
    time, and the device's idle time named by the host span it falls in
    (a span over the whole window names nothing)."""
    lo, hi = window
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    spans = [sp for sp in trace.host_spans if not (sp[1] <= lo and
                                                   sp[2] >= hi)]
    busy, exposed = [], []
    by_name: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for dev in trace.devices:
        coll, other = split_ops(dev.ops, dev.kinds)
        coll_u = union(clip(coll, lo, hi))
        busy_u = union(coll_u + union(clip(other, lo, hi)))
        busy.append(total(busy_u))
        # containers are judged over every op: a loop whose body holds
        # only collectives hides them as much as one that holds others
        inner = set(leaves((s, e) for _, s, e in dev.ops))
        exposed.append(total(subtract(coll_u, union(clip(
            [o for o in other if o in inner], lo, hi)))))
        for name, s, e in dev.ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_name[name] = by_name.get(name, 0.0) + d
        gaps = subtract([(lo, hi)], busy_u)
        for name, secs in _name_gaps(gaps, spans).items():
            idle_by[name] = idle_by.get(name, 0.0) + secs
    n = len(trace.devices)
    top = sorted(((k, v / n * 1e-9) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])[:n_top]
    idle = sorted(((k, v / n * 1e-9) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])[:n_top]
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / n * 1e-9,
                     collective_exposed_s=sum(exposed) / n * 1e-9,
                     top_ops=top, idle_by_host=idle)


def _name_gaps(gaps: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Attribute idle time to the host spans it falls in.  The spans are
    taken as a sequence of disjoint stretches in start order (a span that
    overlaps the one before starts where that one ended); idle time in no
    span is ``host.other``.  One sweep over both sorted lists."""
    segs: List[Tuple[str, float, float]] = []
    for name, s, e in sorted(spans, key=lambda sp: sp[1]):
        if segs:
            s = max(s, segs[-1][2])
        if e > s:
            segs.append((name, s, e))
    out: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][2] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][1] < ge:
            name, s, e = segs[k]
            t = min(e, ge) - max(s, gs)
            if t > 0:
                out[name] = out.get(name, 0.0) + t
                covered += t
            k += 1
        rest = (ge - gs) - covered
        if rest > 0:
            out["host.other"] = out.get("host.other", 0.0) + rest
    return out
