"""Reduction of a profiler trace to the engine's own step programs and spans.

Reads the same ``.xplane.pb`` as ``tracereduce``.  On each device plane it
takes the events of the line named ``XLA Modules``: one per run of a
compiled program, named ``jit_<function>(<hash>)``, kept by the name with
the hash stripped (``jit_engine_decode``).  On the host it takes the spans
whose names start with ``engine.``: those of the engine's recorder
(``repro.runtime.tracing``), nested one tree per step, and the harness's
own ``engine.step.*`` annotations around them.  Each stretch of device idle
time is put down to the innermost of those spans that covers it.

The device's events do not lie on the host's clock: on a TPU v5e each
program run showed up 0.5-1.5 ms before the host enqueued it.  Every run
carries a ``run_id`` that the host's ``DoEnqueueProgram`` and
``CompleteCallbacks`` events carry too, so ``load`` moves each device's
events later by the least shift that puts no run before its enqueue, and
keeps the largest shift that puts no run after its completion callback as
the bound of what the alignment knows.

A trace of a program without the recorder or the named programs holds none
of these, and the readers below then return None.  Nothing here edits
``tracereduce``; it reuses its ``union``, ``clip`` and ``subtract``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import tracereduce as TR

MODULES_LINE = "XLA Modules"
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"
SPAN_PREFIX = "engine."
STEP = "engine.step"
DECODE_PROGRAM = "jit_engine_decode"
_HASH = re.compile(r"\(\d+\)$")

NamedInterval = Tuple[str, float, float]


@dataclasses.dataclass
class ProgramTrace:
    trace: TR.Trace                    # device ops, and every host span
    modules: List[List[NamedInterval]]  # per device, in device order
    shift_ns: List[Tuple[float, float]]  # per device: applied, largest


def _run_id(event) -> Optional[str]:
    return next((str(v) for k, v in event.stats if k == "run_id"), None)


def clock_shift(runs: Sequence[Tuple[str, float, float]],
                enqueued: Dict[str, float], completed: Dict[str, float]
                ) -> Tuple[float, float]:
    """(least, largest) shift of a device's runs ``(run_id, start, end)``
    that starts none before the host enqueued it and ends none after the
    host's completion callback began; (0, 0) with nothing to match."""
    lo = [enqueued[r] - s for r, s, _ in runs if r in enqueued]
    hi = [completed[r] - e for r, _, e in runs if r in completed]
    return (max(lo), min(hi)) if lo and hi else (0.0, 0.0)


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    trace = TR.load(path)
    runs: Dict[str, List[Tuple[str, float, float, Optional[str]]]] = {}
    enqueued: Dict[str, float] = {}
    completed: Dict[str, float] = {}
    for plane in ProfileData.from_file(path).planes:
        if TR.DEVICE_PLANE.match(plane.name):
            runs[plane.name] = [
                (_HASH.sub("", e.name), float(e.start_ns), float(e.end_ns),
                 _run_id(e))
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUED:
                        r = _run_id(e)
                        enqueued[r] = min(enqueued.get(r, e.start_ns),
                                          float(e.start_ns))
                    elif e.name == COMPLETED:
                        r = _run_id(e)
                        completed[r] = max(completed.get(r, e.start_ns),
                                           float(e.start_ns))
    modules, shifts = [], []
    for dev in trace.devices:
        dev_runs = runs.get(dev.name, [])
        shift = clock_shift([(r, s, e) for _, s, e, r in dev_runs],
                            enqueued, completed)
        d = shift[0]
        dev.ops = [(n, s + d, e + d) for n, s, e in dev.ops]
        modules.append(sorted(((n, s + d, e + d) for n, s, e, _ in dev_runs),
                              key=lambda m: m[1]))
        shifts.append(shift)
    return ProgramTrace(trace, modules, shifts)


def innermost(spans: Sequence[NamedInterval]) -> List[NamedInterval]:
    """Disjoint pieces of the union of nested spans, each named by the
    innermost span over it, in time order.  A span that runs past the one
    it starts in is cut at that one's end."""
    out: List[NamedInterval] = []
    stack: List[list] = []                 # [name, end, cursor]

    def pop():
        name, end, cursor = stack.pop()
        if cursor < end:
            out.append((name, cursor, end))

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            top = stack[-1]
            e = min(e, top[1])
            if s > top[2]:
                out.append((top[0], top[2], s))
            top[2] = max(top[2], e)
        if e > s:
            stack.append([name, e, s])
    while stack:
        pop()
    return sorted(out, key=lambda p: p[1])


def _overlap_by_name(gaps: Sequence[TR.Interval],
                     pieces: Sequence[NamedInterval]) -> Dict[str, float]:
    """Time of the sorted disjoint ``gaps`` inside each sorted disjoint
    named piece, summed by name."""
    out: Dict[str, float] = {}
    i = 0
    for name, s, e in pieces:
        while i < len(gaps) and gaps[i][1] <= s:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < e:
            j += 1
        t = TR.total(TR.clip(gaps[i:j], s, e))
        if t > 0:
            out[name] = out.get(name, 0.0) + t
    return out


@dataclasses.dataclass
class ProgramReduction:
    window_s: float
    shift_ms: Tuple[float, float]      # device clock moved, mean; largest
    idle_s: float                      # device idle in the window
    idle_by_span: Dict[str, float]     # innermost ``engine.`` span, seconds
    idle_outside_s: float              # idle under no ``engine.`` span
    steps: int                         # ``engine.step`` spans in the window
    step_idle_s: float                 # idle inside those steps
    modules: Dict[str, Tuple[int, float]]   # runs, device s; all devices

    @property
    def attributed_s(self) -> float:
        return sum(self.idle_by_span.values())


def reduce(pt: ProgramTrace, window: TR.Interval) -> ProgramReduction:
    """Idle time by innermost program span and inside whole ``engine.step``
    spans of the window (means over devices), and each program's runs that
    lie in the window with their device time (summed over devices)."""
    lo, hi = window
    spans = [sp for sp in pt.trace.host_spans
             if sp[0].startswith(SPAN_PREFIX) and sp[2] > lo and sp[1] < hi]
    pieces = innermost([(n, max(s, lo), min(e, hi)) for n, s, e in spans])
    covered = TR.union((s, e) for _, s, e in pieces)
    whole_steps = [(s, e) for n, s, e in spans
                   if n == STEP and s >= lo and e <= hi]
    steps = TR.union(whole_steps)
    n_dev = len(pt.trace.devices)
    idle = outside = step_idle = 0.0
    by_span: Dict[str, float] = {}
    for dev in pt.trace.devices:
        coll, other = TR.split_ops(dev.ops)
        busy = TR.union(TR.union(TR.clip(coll, lo, hi)) +
                        TR.union(TR.clip(other, lo, hi)))
        gaps = TR.subtract([(lo, hi)], busy)
        idle += TR.total(gaps)
        outside += TR.total(TR.subtract(gaps, covered))
        step_idle += TR.total(gaps) - TR.total(TR.subtract(gaps, steps))
        for name, t in _overlap_by_name(gaps, pieces).items():
            by_span[name] = by_span.get(name, 0.0) + t
    modules: Dict[str, Tuple[int, float]] = {}
    for dev_modules in pt.modules:
        for name, s, e in dev_modules:
            if s >= lo and e <= hi:
                n, t = modules.get(name, (0, 0.0))
                modules[name] = (n + 1, t + (e - s) * 1e-9)
    return ProgramReduction(
        window_s=(hi - lo) * 1e-9,
        shift_ms=(sum(a for a, _ in pt.shift_ns) / n_dev * 1e-6,
                  min(b for _, b in pt.shift_ns) * 1e-6),
        idle_s=idle / n_dev * 1e-9,
        idle_by_span={k: v / n_dev * 1e-9 for k, v in by_span.items()},
        idle_outside_s=outside / n_dev * 1e-9, steps=len(whole_steps),
        step_idle_s=step_idle / n_dev * 1e-9, modules=modules)


# ---- what the per-layer metrics read -------------------------------------

def decode_device_ms(red: Optional[ProgramReduction]) -> Optional[float]:
    """Mean device time of one run of the decode program, in ms."""
    n, t = (red.modules.get(DECODE_PROGRAM, (0, 0.0)) if red else (0, 0.0))
    return t / n * 1e3 if n else None


def host_idle_ms(red: Optional[ProgramReduction]) -> Optional[float]:
    """Device idle inside ``engine.step`` spans, per step, in ms."""
    if not red or not red.steps:
        return None
    return red.step_idle_s / red.steps * 1e3


def _in_window(t1_ns: int, w0: float, w1: float) -> bool:
    return w0 <= t1_ns * 1e-9 < w1


def admit_span_ms(export: Optional[dict], w0: float, w1: float
                  ) -> Optional[float]:
    """Mean ``engine.admit`` span that ended in the window, in ms."""
    ts = [s.t1_ns - s.t0_ns for s in (export or {}).get("spans", ())
          if s.name == "engine.admit" and _in_window(s.t1_ns, w0, w1)]
    return sum(ts) / len(ts) * 1e-6 if ts else None


def batch_occupancy(export: Optional[dict], w0: float, w1: float
                    ) -> Optional[float]:
    """Mean share of slots decoding, over the steps that ended in the
    window and admitted nothing, in %."""
    occ = [st["active"] / st["n_slots"] for st in
           (export or {}).get("steps", ())
           if not st["admitted"] and _in_window(st["t1_ns"], w0, w1)]
    return sum(occ) / len(occ) * 100 if occ else None


def report(red: ProgramReduction, export: Optional[dict], w0: float,
           w1: float) -> List[str]:
    """Lines for a traced run's log: idle per step by innermost span, and
    the window's longest step with its children's times."""
    lines = [f"device clock moved {red.shift_ms[0]:.3f} ms later onto the "
             f"host's (at most {red.shift_ms[1]:.3f} ms); device idle "
             f"{red.idle_s * 1e3:.3f} ms of {red.window_s * 1e3:.3f} ms "
             f"traced: {red.attributed_s * 1e3:.3f} ms under program spans, "
             f"{red.idle_outside_s * 1e3:.3f} ms outside them"]
    if red.steps:
        split = ", ".join(
            f"{k} {v / red.steps * 1e3:.3f}" for k, v in
            sorted(red.idle_by_span.items(), key=lambda kv: -kv[1]))
        lines.append(f"idle per step over {red.steps} traced steps (ms): "
                     f"{split}")
    spans = (export or {}).get("spans", ())
    steps = [s for s in spans if s.name == STEP and
             _in_window(s.t1_ns, w0, w1)]
    if steps:
        top = max(steps, key=lambda s: s.t1_ns - s.t0_ns)
        kids = ", ".join(
            f"{s.name}{'' if s.rid is None else f'[{s.rid}]'} "
            f"{(s.t1_ns - s.t0_ns) * 1e-6:.3f}"
            for s in sorted((s for s in spans if s.parent == top.sid),
                            key=lambda s: s.t0_ns))
        lines.append(f"longest engine.step "
                     f"{(top.t1_ns - top.t0_ns) * 1e-6:.3f} ms at "
                     f"{top.t0_ns * 1e-9 - w0:.3f} s into the window "
                     f"(ms): {kids}")
    return lines
