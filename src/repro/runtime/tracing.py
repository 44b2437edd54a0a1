"""In-program spans and per-step counters of the serving engine.

An :class:`EngineTrace` handed to :class:`~repro.runtime.engine.ServeEngine`
records one tree of spans per engine step (``engine.step`` at the root)
and the step's counters, in memory, for a reader to take with
:meth:`EngineTrace.export` when the run ends.  Times are
``time.perf_counter_ns()`` readings that the engine takes once at each
boundary and passes in, so the engine's request times and the spans share
one clock; that is also the clock of ``time.perf_counter()``.

Each open span also holds a ``jax.profiler.TraceAnnotation`` of its name,
so while a profiler session is open the span lands in the device trace,
on the device's clock.

The record is bounded (:data:`CAPACITY` spans, sized for a 40 s window of
about 1,000 steps of up to 8 spans): past it the oldest records go, and
``dropped`` counts them.
"""

from __future__ import annotations

import collections
from typing import Any, Deque, Dict, List, NamedTuple, Optional

import jax

CAPACITY = 8192


class Span(NamedTuple):
    sid: int                      # ordinal of the span's opening
    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[int]         # sid of the enclosing span
    rid: Optional[int]            # request the span serves, if one


class EngineTrace:
    """Spans and per-step counters of one engine.  ``open``/``close`` nest:
    a span opened while another is open is its child, and the outermost
    span of a tree is a step, whose counters ``count`` sets."""

    def __init__(self, capacity: int = CAPACITY):
        self.spans: Deque[Span] = collections.deque(maxlen=capacity)
        self.steps: Deque[Dict[str, Any]] = collections.deque(maxlen=capacity)
        self._n_closed = 0
        self._n_opened = 0
        self._open: List[tuple] = []      # (sid, name, t0_ns, rid, annotation)
        self._counts: Dict[str, int] = {}

    def open(self, name: str, t_ns: int, rid: Optional[int] = None) -> None:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append((self._n_opened, name, t_ns, rid, ann))
        self._n_opened += 1

    def close(self, t_ns: int) -> None:
        sid, name, t0, rid, ann = self._open.pop()
        ann.__exit__(None, None, None)
        parent = self._open[-1][0] if self._open else None
        self.spans.append(Span(sid, name, t0, t_ns, parent, rid))
        self._n_closed += 1
        if parent is None:
            self.steps.append(dict(self._counts, span=sid, t0_ns=t0,
                                   t1_ns=t_ns))
            self._counts = {}

    def count(self, **fields: int) -> None:
        """Set counters of the step now open."""
        self._counts.update(fields)

    @property
    def dropped(self) -> int:
        return self._n_closed - len(self.spans)

    def export(self) -> Dict[str, Any]:
        """Closed spans (in closing order), the steps' counters with their
        root span's times, and how many spans the bound dropped."""
        return {"spans": list(self.spans), "steps": list(self.steps),
                "dropped": self.dropped}
