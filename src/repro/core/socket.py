"""The accelerator socket (ESP) as the single, plan-driven communication API.

ESP's socket decouples an accelerator from the SoC: it provides DMA,
address translation, interrupts, and config registers, plus (this paper)
the per-transfer ``user`` field and a small LUT that *virtualizes* peer
indices into tile coordinates.  Here it is the one place every on-chip
transfer goes through:

* model / runtime / example code issues a transfer from a typed
  :class:`~repro.core.comm.TransferDescriptor` — never by calling
  ``p2p_*`` / ``multicast_*`` (a CI grep gate forbids importing those
  helpers outside ``core/`` and ``tests/``) or raw GSPMD collectives
  (by convention — the gate cannot see ``jax.lax.*`` call sites);
* the socket resolves the *mode* against the active
  :class:`~repro.core.comm.CommPlan` (``use_rules(..., comm_plan=...)``
  context or an explicit plan), keyed by
  :func:`~repro.core.comm.base_transfer_name`;
* the transfer is encoded as the read/write user-field instruction
  (:mod:`repro.core.isa` — the format ``kernels/dma_isa`` consumes) and
  dispatched to the MEM / P2P / MCAST implementation, including the
  Pallas multicast-stream fast path when constraints allow;
* C3 sync fencing (``desc.sync``) is folded in here — the producer
  aggregates consumer requests on the sync region before the bulk moves —
  instead of being left to callers;
* every dispatch appends an :class:`IssueRecord` to a bounded trace-time
  log, so dryrun artifacts report the *issued* mode per site, not just
  the planned one.

:class:`StageRegistry` is the LUT — peers are addressed by *name*
("encoder", "decoder", "expert_shard"), never by mesh coordinate.  Peer
ranks may also be passed as traced values (``peer_rank``): the encoded
user field stays the stable *virtual* index while the LUT value rides in
as a step argument, so ``remap`` retargets a transfer without retracing
or relowering the stage function.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp

from repro.core import isa
from repro.core import multicast as MC
from repro.core import p2p as P2P
from repro.core import sync as SYNC
from repro.core.comm import (CommMode, CommPlan, CommRequest, FaultError,
                             TransferDescriptor,
                             UnregisteredFusionTargetError,
                             base_transfer_name, known_fusion_targets)
from repro.core.sharding import current_comm_plan, logical_constraint


@dataclasses.dataclass
class StageRegistry:
    """Virtualization LUT: name / virtual index -> rank on the stage axis.

    The paper: 'A small, configurable lookup table in the socket encodes
    the tile coordinates for each index, so that these values can be
    virtualized.'  The *virtual* index of a name (1-based registration
    order; 0 is reserved for the MEM encoding) is what the user field
    carries — ``remap`` rewrites the LUT entry, never the instruction."""
    axis_name: str
    table: Dict[str, int] = dataclasses.field(default_factory=dict)

    def register(self, name: str, rank: int) -> int:
        self.table[name] = rank
        return self.virtual_of(name)

    def rank_of(self, name: str) -> int:
        return self.table[name]

    def virtual_of(self, name: str) -> int:
        """1-based LUT index of ``name`` (stable under remap)."""
        return list(self.table).index(name) + 1

    def remap(self, name: str, new_rank: int):
        """Retarget a peer without touching accelerator code (e.g. after
        an elastic re-mesh migrates a stage)."""
        if name not in self.table:
            raise KeyError(name)
        self.table[name] = new_rank


# ------------------------------------------------------------- issue log ----

@dataclasses.dataclass(frozen=True)
class IssueRecord:
    """One socket dispatch, recorded at trace time: which mode was
    *issued* at the site (vs merely planned), through which
    implementation, under which user-field encoding."""
    site: str                 # call-site label (descriptor site_label)
    name: str                 # base transfer name (the plan key)
    channel: str              # "read" | "write" | "exchange" | "reduce" |
    #                           "gather_matmul" | "reduce_scatter"
    planned: str              # mode the active plan assigned (or hint)
    issued: str               # mode actually dispatched
    user: int                 # encoded user field
    nbytes: int
    impl: str                 # "constraint"|"ppermute"|"fork_tree"|...
    sync: bool = False
    # machine-readable reason whenever issued != planned: a topology
    # degradation ("no stage axis: ..."), a pinned-mode override
    # ("reduction: ..."), or a retry-ladder downgrade ("ladder
    # FUSED_RING->P2P: ...").  Never empty when issued and planned
    # disagree — commcheck's ``degraded-without-reason`` rule is the
    # static mirror of this contract.
    degraded_reason: Optional[str] = None
    # an OVERLAPPED implementation dispatched: the FUSED_RING kernels
    # (comm overlapped with the consumer matmul) or the double-buffered
    # multicast stream.  Strictly an *issued* property — a planner
    # decision may be priced fused (PlanDecision.fused, the platform's
    # capability) while this site's serial lowering records False.
    fused: bool = False
    # request/step epoch the issue belongs to (``issue_epoch``): under
    # continuous batching, prefill and decode traces (or two requests)
    # hit the *same* site label, and a site-keyed summary would let the
    # later trace overwrite the earlier record.  None outside an epoch
    # scope — single-trace dryruns keep their bare site keys.
    epoch: Optional[str] = None

    @property
    def degraded(self) -> Optional[str]:
        """Pre-ladder alias of ``degraded_reason`` (kept for artifact
        consumers written against the old field name)."""
        return self.degraded_reason


class _IssueLog(threading.local):
    def __init__(self):
        # bounded: tracing in long test sessions must not grow unbounded
        self.records = collections.deque(maxlen=4096)
        # the ambient (site, epoch) scope: continuous batching traces
        # prefill and decode steps that share site labels; the active
        # epoch tags each record so summaries stay audit-accurate
        self.epoch: Optional[str] = None


_LOG = _IssueLog()


def reset_issue_log() -> None:
    _LOG.records.clear()
    _LOG.epoch = None


def current_issue_epoch() -> Optional[str]:
    return _LOG.epoch


@contextlib.contextmanager
def issue_epoch(label: Optional[str]):
    """Scope trace-time issue records by (site, epoch).

    The serving engine traces its prefill and batched-decode steps
    separately, and both hit shared site labels (``moe.dispatch``, the
    weight-gather sites).  Without a scope, :func:`issued_modes` is
    last-write-wins per site and the earlier trace's record silently
    disappears from artifacts.  Inside ``issue_epoch("prefill")`` every
    record is stamped with the epoch and summarised under
    ``"<site>@prefill"`` — two epochs at one site coexist."""
    prev = _LOG.epoch
    _LOG.epoch = label
    try:
        yield
    finally:
        _LOG.epoch = prev


def _summary_key(r: IssueRecord) -> str:
    return r.site if r.epoch is None else f"{r.site}@{r.epoch}"


def issued_records() -> List[IssueRecord]:
    return list(_LOG.records)


def issued_modes() -> Dict[str, Dict[str, Any]]:
    """Per-(site, epoch) summary for dryrun artifacts: last record per
    scope key (a relower of the *same* step overwrites the earlier
    trace's entry; records from distinct :func:`issue_epoch` scopes —
    prefill vs decode, request A vs request B — keep separate
    ``"<site>@<epoch>"`` keys instead of clobbering each other)."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in _LOG.records:
        out[_summary_key(r)] = {
            "tensor": r.name, "channel": r.channel, "planned": r.planned,
            "issued": r.issued, "user_field": r.user, "impl": r.impl,
            "nbytes": r.nbytes, "degraded": r.degraded_reason,
            "degraded_reason": r.degraded_reason, "fused": r.fused,
            "epoch": r.epoch,
        }
    return out


def mismatched_sites(plan: Optional[CommPlan]) -> List[Dict[str, str]]:
    """The logged sites whose issued mode silently disagrees with the
    plan, for the CLI summaries — each entry carries the site label, the
    plan key, and the planned vs issued modes.  An explicitly *degraded*
    issue (no stage axis / no peers on this topology) conforms by
    definition — degradation to MEM is the paper's own rule for
    unrealizable direct transfers — and a P2P/MCAST write pair is one
    wire transaction (the ``user=1`` degeneracy)."""
    if plan is None:
        return []
    direct = {CommMode.P2P.name, CommMode.MCAST.name}
    out: List[Dict[str, str]] = []
    for r in _LOG.records:
        planned = plan.mode(base_transfer_name(r.name)).name
        if r.issued == planned or r.degraded_reason is not None:
            continue
        if r.issued in direct and planned in direct:
            continue
        out.append({"site": r.site, "tensor": r.name,
                    "planned": planned, "issued": r.issued})
    return out


def issued_matches_plan(plan: Optional[CommPlan]) -> bool:
    """True when every logged site issued the mode the plan assigned
    (see :func:`mismatched_sites` for the conformance rules and the
    offending sites when this is False)."""
    return not mismatched_sites(plan)


def issue_observations(plan: Optional[CommPlan] = None
                       ) -> List[Dict[str, Any]]:
    """Export the trace-time issue log as plain measurement dicts for the
    calibration loop (``repro.calib.measure`` lifts them into typed
    ``Observation`` records; ``planner.refine_plan_from_measurements``
    consumes them directly — core stays import-free of ``repro.calib``).

    One dict per logged record, ``kind == "issue"``: the planned vs issued
    mode at the site, payload size, and the machine-readable degradation
    reason (``None`` marks a *silent* mismatch, the re-pricing trigger).
    With ``plan``, ``planned`` is re-read from the plan in force (a record
    traced under a hint can predate the resolved plan)."""
    out: List[Dict[str, Any]] = []
    for r in _LOG.records:
        planned = (plan.mode(base_transfer_name(r.name)).name
                   if plan is not None else r.planned)
        out.append({
            "kind": "issue", "site": _summary_key(r), "name": r.name,
            "planned": planned, "issued": r.issued, "nbytes": r.nbytes,
            "channel": r.channel, "impl": r.impl,
            "degraded_reason": r.degraded_reason, "epoch": r.epoch,
        })
    return out


def record_implicit_issue(name: str, *, planned: CommMode, issued: CommMode,
                          nbytes: int = 0, impl: str = "xla",
                          reason: Optional[str] = None,
                          site: Optional[str] = None) -> None:
    """Log a transfer the compiler issues on the socket's behalf (e.g. the
    rule-gated weight all-gather: the sharding rules, not a call site,
    generate it).  Runtime step factories call this at trace time so the
    issue log covers implicit transfers too."""
    # the user field of a compiler-issued transfer records the *triad
    # class* (0 = MEM, 1 = P2P, 2 = MCAST — consistent with
    # mode_from_write_field), not a destination count the socket never saw
    _LOG.records.append(IssueRecord(
        site=site or name, name=base_transfer_name(name), channel="rules",
        planned=planned.name, issued=issued.name,
        user=issued.value, nbytes=nbytes, impl=impl,
        degraded_reason=reason if issued is not planned else None,
        epoch=_LOG.epoch))


# ----------------------------------------------- retry / degradation ladder ----

# the typed downgrade order every fallible dispatch walks: the overlapped
# Pallas rung first, then the serial collective under the same direct
# verdict, then the same collective charged to the memory round-trip (the
# accounting of last resort — data still moves; a MEM rung cannot
# *substitute* a different dataflow without changing numerics).  A rung
# that keeps failing after its bounded retries hands to the next with a
# machine-readable ``degraded_reason``; past the last rung the socket
# raises :class:`~repro.core.comm.FaultError` so the fault-tolerant
# runner can checkpoint-restore instead of crashing opaquely mid-trace.
DEGRADATION_LADDER: Tuple[str, ...] = ("FUSED_RING", "P2P", "MEM")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry-with-backoff for one ladder rung.

    ``max_attempts`` counts *total* tries of a rung (1 = no retry);
    between tries the socket sleeps ``backoff_s * multiplier**k`` capped
    at ``max_backoff_s``.  ``sleep`` is injectable so tests (and the
    chaos harness) can observe the schedule without wall-clock waits.
    A socket constructed without a policy (the default) never catches:
    dispatch errors propagate exactly as before the ladder existed."""
    max_attempts: int = 3
    backoff_s: float = 0.05
    multiplier: float = 2.0
    max_backoff_s: float = 1.0
    sleep: Callable[[float], None] = time.sleep

    def schedule(self) -> Iterator[float]:
        """The sleep preceding each retry: ``max_attempts - 1`` entries,
        geometric from ``backoff_s``, each capped at ``max_backoff_s``."""
        delay = self.backoff_s
        for _ in range(max(self.max_attempts - 1, 0)):
            yield min(delay, self.max_backoff_s)
            delay *= self.multiplier


# ----------------------------------------------------------------- socket ----

PeerArg = Union[None, str, int, jax.Array]


class AcceleratorSocket:
    """Per-stage communication services.  Use inside shard_map over the
    stage axis (``registry.axis_name`` or ``axis_name``); without an axis
    the socket still issues — every transfer degrades to the MEM path,
    which is exactly what a topology with no direct path provides.

    ``use_kernels=True`` enables the Pallas fast paths (multicast stream)
    when the payload satisfies the kernel's constraints; ``interpret``
    is forwarded to the kernel (tests pass ``interpret_params()``).

    ``retry`` binds a :class:`RetryPolicy`: the fallible kernel dispatch
    paths then walk the :data:`DEGRADATION_LADDER` (bounded retries per
    rung, machine-readable ``degraded_reason`` per downgrade,
    :class:`~repro.core.comm.FaultError` past the last rung) instead of
    letting a trace-time kernel error crash the step opaquely.  Without a
    policy the socket behaves exactly as before: nothing is caught.
    ``fence_timeout_s > 0`` arms a stall watchdog on the C3 sync fence —
    a hung barrier becomes a ``FaultError`` instead of a deadlock.  Note
    ``resolve_mode`` stays pure (and its
    ``UnregisteredFusionTargetError`` always propagates): retry and
    degradation apply to *dispatch*, never to plan resolution.
    """

    def __init__(self, registry: Optional[StageRegistry] = None,
                 plan: Optional[CommPlan] = None, *,
                 axis_name: Optional[str] = None,
                 use_kernels: bool = False, interpret=None,
                 retry: Optional[RetryPolicy] = None,
                 fence_timeout_s: float = 0.0):
        self.registry = registry
        self.axis_name = axis_name or (registry.axis_name if registry else None)
        self._plan = plan
        self.use_kernels = use_kernels
        self.interpret = interpret
        self.retry = retry
        self.fence_timeout_s = fence_timeout_s

    # ------------------------------------------------------- resolution ----
    def plan(self) -> Optional[CommPlan]:
        """The plan in force at issue time: an explicitly bound plan wins,
        else the ambient ``use_rules(..., comm_plan=...)`` context."""
        return self._plan if self._plan is not None else current_comm_plan()

    def resolve_mode(self, desc: TransferDescriptor,
                     hint: Optional[CommMode] = None) -> CommMode:
        """Plan-driven mode for a descriptor: exact name first, then the
        base archetype; a transfer the plan does not cover follows the
        caller's ``hint`` (manual/flag-driven behaviour), else the plan
        default (MEM).  First issue also validates ``fused_with``: a
        dangling target used to silently never fuse — now it raises
        (:class:`~repro.core.comm.UnregisteredFusionTargetError`, the
        runtime mirror of commcheck's ``descriptor-dangling-fused``)."""
        if desc.fused_with is not None and \
                desc.fused_with not in known_fusion_targets():
            raise UnregisteredFusionTargetError(
                f"descriptor {desc.site_label!r}: fused_with="
                f"{desc.fused_with!r} was never registered at trace time — "
                f"the transfer would silently take the unfused path. "
                f"Register the consumer matmul with "
                f"core.comm.register_fusion_target, or fix the label "
                f"(known targets: "
                f"{sorted(known_fusion_targets()) or 'none'})")
        plan = self.plan()
        if plan is not None:
            if desc.name in plan.modes:
                return plan.modes[desc.name]
            base = base_transfer_name(desc.name)
            if base in plan.modes:
                return plan.modes[base]
        if hint is not None:
            return hint
        return plan.default if plan is not None else CommMode.MEM

    def resolve(self, desc: TransferDescriptor, nbytes: int, channel: str,
                hint: Optional[CommMode] = None,
                word_bytes: Optional[int] = None
                ) -> Tuple[CommMode, CommRequest, isa.DmaInstruction]:
        """Full issue-site resolution: plan mode -> control-channel beat ->
        ISA instruction.  ``word_bytes`` is the tensor's dtype itemsize
        (the descriptor's own ``word_bytes`` overrides it; 4 when neither
        is known).  This is the per-dispatch overhead the
        ``socket_dispatch_overhead`` benchmark row measures."""
        mode = self.resolve_mode(desc, hint)
        word = desc.word_bytes or word_bytes or 4
        length = max(nbytes // word, 1)
        source = dests = None
        if mode is not CommMode.MEM and self.registry is not None:
            if desc.source is not None:
                source = self.registry.virtual_of(desc.source)
            if desc.dests:
                dests = tuple(self.registry.virtual_of(n) for n in desc.dests)
        # the instruction encodes the transfer as it will actually issue: a
        # direct verdict with no LUT peers on this topology degrades to the
        # memory encoding (user field 0) — the paper's own rule
        if channel == isa.CH_READ:
            enc = mode if source is not None else CommMode.MEM
            req = CommRequest(length, word, enc,
                              source=source if enc is not CommMode.MEM
                              else None)
        else:
            enc = mode if dests else CommMode.MEM
            req = CommRequest(length, word, enc, dests=dests or ())
        return mode, req, isa.encode(req, channel)

    def _nbytes(self, x) -> int:
        return int(x.size) * x.dtype.itemsize

    def _log(self, desc, channel, planned, issued, user, nbytes, impl,
             degraded=None, fused=False):
        _LOG.records.append(IssueRecord(
            site=desc.site_label, name=base_transfer_name(desc.name),
            channel=channel, planned=planned.name, issued=issued.name,
            user=user, nbytes=nbytes, impl=impl, sync=desc.sync,
            degraded_reason=degraded, fused=fused, epoch=_LOG.epoch))

    # ------------------------------------------- retry / degradation ladder ----
    def _attempt(self, thunk):
        """Run one ladder rung under the bound retry policy.  No policy:
        the thunk runs bare and errors propagate (legacy behaviour).
        With a policy: bounded retry with backoff — returns
        ``(True, result)`` on success, ``(False, (attempts, last_err))``
        once the rung is exhausted.  ``FaultError`` is never retried:
        it is already the ladder's own verdict (e.g. a fence watchdog
        firing inside the rung), not a transient."""
        if self.retry is None:
            return True, thunk()
        delays = self.retry.schedule()
        attempts = 0
        while True:
            attempts += 1
            try:
                return True, thunk()
            except FaultError:
                raise
            except Exception as err:
                delay = next(delays, None)
                if delay is None:
                    return False, (attempts, err)
                self.retry.sleep(delay)

    def _ladder(self, desc, channel, planned, nbytes, rungs):
        """Dispatch through the degradation ladder.  ``rungs`` is an
        ordered list of ``(rung_name, issued_mode, user, impl, fused,
        thunk)`` — names drawn from :data:`DEGRADATION_LADDER`.  Each
        rung runs under :meth:`_attempt`; a failure downgrades to the
        next rung carrying the accumulated machine-readable reason, and
        the last rung's failure raises ``FaultError`` (the runner's
        recovery signal)."""
        reason = None
        for i, (rung, issued, user, impl, fused, thunk) in enumerate(rungs):
            ok, res = self._attempt(thunk)
            if ok:
                self._log(desc, channel, planned, issued, user, nbytes, impl,
                          degraded=reason, fused=fused)
                return res
            attempts, err = res
            if i + 1 == len(rungs):
                raise FaultError(
                    f"socket {desc.site_label!r}: degradation ladder "
                    f"exhausted at rung {rung} after {attempts} attempt(s): "
                    f"{type(err).__name__}: {err}") from err
            hop = (f"ladder {rung}->{rungs[i + 1][0]}: {rung} failed after "
                   f"{attempts} attempt(s) ({type(err).__name__}: {err})")
            reason = f"{reason}; {hop}" if reason else hop

    def _peer(self, value: PeerArg, fallback_name: Optional[str]):
        """Resolve a peer argument: name -> LUT rank (static), int ->
        static rank, traced array -> dynamic rank; None falls back to the
        descriptor's name."""
        if value is None:
            value = fallback_name
        if value is None:
            return None
        if isinstance(value, str):
            # a named peer without a LUT cannot resolve: the caller's
            # guard degrades the transfer to the MEM path
            if self.registry is None:
                return None
            return self.registry.rank_of(value)
        return value

    def peer_rank(self, name: str) -> jnp.ndarray:
        """The LUT entry for ``name`` as a *value* (pass it into a jitted
        stage function): the transfer then follows a later ``remap``
        without retracing — the paper's virtualization."""
        return jnp.int32(self.registry.rank_of(name))

    @staticmethod
    def _is_static(rank) -> bool:
        import numpy as np
        return isinstance(rank, (int, np.integer))

    def _fence(self, x, mode: CommMode):
        """C3 folded in: before a direct transfer, exchange the sync-region
        flag (the producer's aggregation of consumer pull requests) and
        order the bulk payload after it.  The MEM path needs no fence —
        the memory round-trip is its own ordering point.  With
        ``fence_timeout_s > 0`` the barrier runs under a stall watchdog:
        a fence that hangs (a peer died mid sync region) surfaces as a
        ``FaultError`` the runner can recover from, not a deadlock."""
        if mode is CommMode.MEM or self.axis_name is None:
            return x
        flag = self._guarded_barrier()
        return SYNC.ordered_after(x, flag)

    def _guarded_barrier(self):
        if self.fence_timeout_s <= 0:
            return SYNC.barrier(self.axis_name)
        box: List[Tuple[str, Any]] = []

        def run():
            try:
                box.append(("ok", SYNC.barrier(self.axis_name)))
            except BaseException as err:  # surfaces in the caller below
                box.append(("err", err))

        t = threading.Thread(target=run, daemon=True, name="socket-fence")
        t.start()
        t.join(self.fence_timeout_s)
        if not box:
            # the daemon thread is abandoned, not killed — but the trace
            # no longer blocks on it, and the runner gets a typed fault
            raise FaultError(
                f"sync fence on axis {self.axis_name!r} stalled past the "
                f"{self.fence_timeout_s:g}s watchdog — peer lost mid "
                f"sync region?")
        tag, val = box[0]
        if tag == "err":
            raise val
        return val

    # -- read channel: user field selects the source -------------------------
    def read(self, x: jax.Array, desc: TransferDescriptor,
             source: PeerArg = None, consumer: PeerArg = None) -> jax.Array:
        """Pull-based read.  MEM: DMA resharding along the *descriptor's*
        logical axes.  P2P: the consumer pulls from the virtualized source
        — both endpoints resolve through the LUT, so retargeting a
        producer is a registry update (and with traced ranks, not even a
        retrace)."""
        hint = CommMode.P2P if desc.pull else None
        nbytes = self._nbytes(x)
        mode, req, instr = self.resolve(desc, nbytes, isa.CH_READ, hint,
                                        word_bytes=x.dtype.itemsize)
        src = self._peer(source, desc.source)
        dst = self._peer(consumer, desc.consumer)
        if self.axis_name is None or src is None or dst is None:
            # no stage axis / no peers on this topology: the only path is
            # through memory — the paper's degradation rule
            degraded = (None if mode is CommMode.MEM else
                        ("no stage axis: direct path unrealizable"
                         if self.axis_name is None
                         else "no source/consumer peers at this site"))
            self._log(desc, "read", mode, CommMode.MEM,
                      0 if degraded else instr.user, nbytes, "constraint",
                      degraded)
            return self._mem(x, desc)
        # peers on a live stage axis: data always moves; the mode selects
        # which path it is charged to (MEM = the emulated memory-tile
        # round-trip; same collective, different accounting and no fence)
        if desc.sync:
            x = self._fence(x, mode)
        issued = CommMode.MEM if mode is CommMode.MEM else CommMode.P2P
        if self._is_static(src) and self._is_static(dst):
            impl = ("mem_roundtrip" if mode is CommMode.MEM else "ppermute")
            self._log(desc, "read", mode, issued, instr.user, nbytes, impl)
            return P2P.p2p_send_recv(x, self.axis_name, int(src), int(dst))
        self._log(desc, "read", mode, issued, instr.user, nbytes,
                  "dynamic_lut")
        return P2P.p2p_send_recv_dynamic(x, self.axis_name, src, dst)

    # -- write channel: user field selects destination count -----------------
    def write(self, x: jax.Array, desc: TransferDescriptor,
              producer: PeerArg = None,
              dests: Optional[Sequence[PeerArg]] = None) -> jax.Array:
        """MEM: DMA to memory (resharding by the descriptor's axes).  One
        dest: unicast P2P (``user=1``).  Several: multicast — the producer
        waits for all consumer pulls (sync region, when ``desc.sync``),
        then sends once (C2).  Dispatches to the Pallas multicast-stream
        kernel when enabled and the payload qualifies."""
        dst_args = list(dests) if dests is not None else list(desc.dests)
        hint = (None if not dst_args else
                (CommMode.P2P if len(dst_args) == 1 else CommMode.MCAST))
        nbytes = self._nbytes(x)
        mode, req, instr = self.resolve(desc, nbytes, isa.CH_WRITE, hint,
                                        word_bytes=x.dtype.itemsize)
        src = self._peer(producer, desc.source)
        if self.axis_name is None or src is None or not dst_args:
            degraded = None
            if mode is not CommMode.MEM:
                degraded = ("no stage axis: direct path unrealizable"
                            if self.axis_name is None
                            else "no destination peers at this site")
            self._log(desc, "write", mode, CommMode.MEM,
                      0 if degraded else instr.user, nbytes, "constraint",
                      degraded)
            return self._mem(x, desc)
        ranks = [self._peer(d, None) for d in dst_args]
        if desc.sync:
            x = self._fence(x, mode)
        # data always moves to the listed peers; a MEM verdict charges the
        # transaction to the memory round-trip (user field 0) but delivery
        # rides the same collective — the socket never drops a transfer
        issued = (CommMode.MEM if mode is CommMode.MEM else
                  (CommMode.P2P if len(ranks) == 1 else CommMode.MCAST))
        mem = mode is CommMode.MEM
        if all(self._is_static(r) for r in ranks) and self._is_static(src):
            ranks = [int(r) for r in ranks]
            if len(ranks) == 1:
                self._log(desc, "write", mode, issued, instr.user, nbytes,
                          "mem_roundtrip" if mem else "ppermute")
                return P2P.p2p_send_recv(x, self.axis_name, int(src),
                                         ranks[0])
            if not mem and self._kernel_ok(x, ranks, int(src)):
                from repro.kernels.multicast_stream import \
                    multicast_stream_local

                # the double-buffered store-and-forward stream IS an
                # overlapped implementation: chunk k forwards while k+1
                # streams — a fused issue.  The ladder below it reissues
                # the same payload through the serial fork tree (identical
                # numbers), last under MEM accounting.
                def kernel():
                    return multicast_stream_local(
                        x, axis_name=self.axis_name, src=int(src),
                        n_chunks=self._kernel_chunks(x),
                        interpret=self.interpret)

                def serial():
                    return MC.multicast_subset(x, self.axis_name, int(src),
                                               ranks)

                return self._ladder(desc, "write", mode, nbytes, [
                    ("FUSED_RING", issued, instr.user,
                     "mcast_stream_kernel", True, kernel),
                    ("P2P", issued, instr.user, "fork_tree", False, serial),
                    ("MEM", CommMode.MEM, 0, "mem_roundtrip", False, serial),
                ])
            self._log(desc, "write", mode, issued, instr.user, nbytes,
                      "mem_roundtrip" if mem else "fork_tree")
            return MC.multicast_subset(x, self.axis_name, int(src), ranks)
        self._log(desc, "write", mode, issued, instr.user, nbytes,
                  "dynamic_lut")
        if len(ranks) == 1:
            return P2P.p2p_send_recv_dynamic(x, self.axis_name, src, ranks[0])
        return MC.multicast_subset_dynamic(x, self.axis_name, src,
                                           jnp.asarray(ranks, jnp.int32))

    # -- exchange: the all-to-all dispatch (each rank both ends) --------------
    def exchange(self, x: jax.Array, desc: TransferDescriptor, *,
                 split_axis: int, concat_axis: int, tiled: bool = False,
                 hint: Optional[CommMode] = None) -> jax.Array:
        """Symmetric dispatch (MoE): every shard writes a distinct slab to
        every peer — per-pair unicast writes with the destination list in
        the header, one issued transfer per source.  The plan decides
        whether this site runs at all (its MEM alternative is a different
        dataflow the *caller* traces), so ``hint`` carries the caller's
        flag-driven mode when no plan is active."""
        assert self.axis_name is not None, "exchange needs a stage axis"
        mode = self.resolve_mode(desc, hint)
        n = jax.lax.axis_size(self.axis_name)
        nbytes = self._nbytes(x)
        word = desc.word_bytes or x.dtype.itemsize
        mem = mode is CommMode.MEM
        req = CommRequest(max(nbytes // word, 1), word, mode,
                          dests=() if mem else tuple(range(1, n)))
        instr = isa.encode(req, isa.CH_WRITE)
        # the dispatch still runs under a MEM verdict (the caller chose
        # this dataflow); it is charged to the memory round-trip, exactly
        # like read/write with peers — issued mode and user field agree
        issued = (CommMode.MEM if mem else
                  (CommMode.P2P if n <= 2 else CommMode.MCAST))
        if desc.sync:
            x = self._fence(x, mode)
        # desc.fused_with here is a *pricing* declaration (the planner
        # hides the dispatch behind the expert matmuls); this site's
        # lowering is one serial all_to_all, so the issue is NOT recorded
        # fused — the flag means an overlapped implementation dispatched
        self._log(desc, "exchange", mode, issued, instr.user, nbytes,
                  "mem_roundtrip" if mem else "all_to_all")
        return jax.lax.all_to_all(x, self.axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=tiled)

    # -- fused MoE chain: dispatch -> expert FFN -> combine -------------------
    def dispatch_expert_ffn(self, toks: jax.Array, ffn,
                            dispatch_desc: TransferDescriptor,
                            combine_desc: TransferDescriptor, *,
                            hint: Optional[CommMode] = None) -> jax.Array:
        """The whole MoE exchange chain as ONE socket dispatch: dispatch
        all-to-all -> per-expert FFN -> mirrored combine all-to-all.

        ``toks`` is destination-major ``(M, E_loc, C, d)`` — slab ``j``
        is this source's capacity buffers for the experts shard ``j``
        owns; ``ffn`` maps ``(E_loc, T, d) -> (E_loc, T, d)``
        token-row-independently (the expert einsums).  Returns the
        combine result ``(M, E_loc, C, d)``: slab ``j`` holds the
        outputs shard ``j`` computed for MY tokens.

        FUSED_RING dispatch: when the plan's verdict is a direct mode,
        kernels are on, and ``dispatch_desc.fused_with`` names the
        expert FFN, the chain runs as a ring pipeline — at offset ``s``
        each shard forwards its slab for peer ``rank+s`` while the slab
        that arrived from ``rank-s`` feeds the expert matmuls, and the
        result rides the mirrored hop home.  Hop ``s+1`` has no data
        dependence on step ``s``'s compute, so the dispatch streams
        behind the FFN exactly like the planner prices it.  ``ffn`` is
        row-independent, so the per-slab pipeline is bit-identical to
        the unfused path (one serial ``all_to_all`` each way around one
        full-batch FFN) — the fallback rungs and the non-fusible path
        below."""
        assert self.axis_name is not None, "dispatch_expert_ffn needs an axis"
        mode = self.resolve_mode(dispatch_desc, hint)
        n = jax.lax.axis_size(self.axis_name)
        fusible = (mode is not CommMode.MEM and self.use_kernels and
                   dispatch_desc.fused_with is not None and
                   isinstance(n, int) and n > 1 and toks.ndim == 4 and
                   toks.shape[0] == n)
        if not fusible:
            # unfused chain: two serial exchanges through the normal
            # socket path (each logs its own site) around one FFN
            recv = self.exchange(toks, dispatch_desc, split_axis=0,
                                 concat_axis=0, hint=hint)
            M, E_loc, C, d = recv.shape
            out = ffn(jnp.moveaxis(recv, 0, 1).reshape(E_loc, M * C, d))
            out = jnp.moveaxis(out.reshape(E_loc, M, C, d), 1, 0)
            return self.exchange(out, combine_desc, split_axis=0,
                                 concat_axis=0, hint=hint)
        nbytes = self._nbytes(toks)
        word = dispatch_desc.word_bytes or toks.dtype.itemsize
        req = CommRequest(max(nbytes // word, 1), word, mode,
                          dests=tuple(range(1, n)))
        instr = isa.encode(req, isa.CH_WRITE)
        if dispatch_desc.sync:
            toks = self._fence(toks, mode)
        issued = CommMode.P2P if n <= 2 else CommMode.MCAST

        def _combine_log(impl, fused):
            # the chain's return hop, recorded under the combine site so
            # artifact consumers see both halves of the exchange
            self._log(combine_desc, "dispatch_chain", mode, issued,
                      instr.user, nbytes, impl, fused=fused)

        def fused():
            out = self._ring_dispatch_ffn(toks, ffn, n)
            _combine_log("ring_dispatch_ffn", True)
            return out

        def serial():
            out = self._serial_dispatch_ffn(toks, ffn)
            _combine_log("all_to_all", False)
            return out

        return self._ladder(dispatch_desc, "dispatch_chain", mode, nbytes, [
            ("FUSED_RING", issued, instr.user, "ring_dispatch_ffn", True,
             fused),
            ("P2P", issued, instr.user, "all_to_all", False, serial),
            ("MEM", CommMode.MEM, 0, "mem_roundtrip", False, serial),
        ])

    def _ring_dispatch_ffn(self, toks, ffn, n: int):
        """The overlapped chain: offset-``s`` ppermute hops around the
        ring, expert FFN on each arriving slab, mirrored hop home.  The
        forward hop at offset ``s+1`` is independent of step ``s``'s
        matmuls — the compiler is free to keep the wire busy under the
        MXU, which is exactly the schedule the planner priced."""
        M, E_loc, C, d = toks.shape
        axis = self.axis_name
        rank = jax.lax.axis_index(axis)
        # step 0: my own slab never touches the wire
        y0 = ffn(jax.lax.dynamic_index_in_dim(toks, rank, 0, keepdims=False))
        back = jnp.zeros((M, E_loc, C, d), y0.dtype)
        back = jax.lax.dynamic_update_index_in_dim(back, y0, rank, 0)
        for s in range(1, M):
            send_to = jax.lax.rem(rank + s, M)
            chunk = jax.lax.dynamic_index_in_dim(toks, send_to, 0,
                                                 keepdims=False)
            # every shard sends its slab for peer (i+s) — so the slab
            # arriving here is what peer (rank-s) packed for my experts
            fwd = [(i, (i + s) % M) for i in range(M)]
            arrived = jax.lax.ppermute(chunk, axis, perm=fwd)
            y = ffn(arrived)
            # mirrored hop: the result returns to its token owner, and
            # peer (rank+s)'s result for MY tokens lands here
            bwd = [(i, (i - s) % M) for i in range(M)]
            mine = jax.lax.ppermute(y, axis, perm=bwd)
            back = jax.lax.dynamic_update_index_in_dim(back, mine, send_to, 0)
        return back

    def _serial_dispatch_ffn(self, toks, ffn):
        """The unfused chain body (no logging — ladder rungs log): one
        all_to_all each way around one full-batch FFN."""
        recv = jax.lax.all_to_all(toks, self.axis_name, split_axis=0,
                                  concat_axis=0)
        M, E_loc, C, d = recv.shape
        out = ffn(jnp.moveaxis(recv, 0, 1).reshape(E_loc, M * C, d))
        out = jnp.moveaxis(out.reshape(E_loc, M, C, d), 1, 0)
        return jax.lax.all_to_all(out, self.axis_name, split_axis=0,
                                  concat_axis=0)

    # -- reduce: fan-in combining, pinned to the memory path ------------------
    def reduce(self, x: jax.Array, desc: TransferDescriptor, *,
               wire_bytes: Optional[int] = None) -> jax.Array:
        """Combining reduction over the stage axis.  The NoC forks
        multicast flits but cannot combine them in flight, so reductions
        always ride the memory path (planner pins them to MEM) — recorded
        as such regardless of what the plan says.  ``wire_bytes``
        overrides the logged byte count when the on-wire payload is
        narrower than the combined tensor (the int8 compressed-gradient
        transport: the wire moves a quarter of what the psum widens to) —
        the issue log must price what *moves*, not what is summed."""
        assert self.axis_name is not None, "reduce needs a stage axis"
        planned = self.resolve_mode(desc, CommMode.MEM)
        nbytes = wire_bytes if wire_bytes is not None else self._nbytes(x)
        self._log(desc, "reduce", planned, CommMode.MEM, 0, nbytes, "psum",
                  degraded=None if planned is CommMode.MEM else
                  "reduction: cannot combine in flight — memory path")
        return jax.lax.psum(x, self.axis_name)

    # -- FUSED_RING: comm fused with the consumer matmul (paper Fig. 6) -------
    def _fused_ring_ok(self, desc: TransferDescriptor, x) -> bool:
        """FUSED_RING preconditions: kernels enabled, the descriptor
        declares its consumer matmul (``fused_with``), a static ring size,
        and a 2-D payload the ring kernels accept.  Anything else takes
        the unfused lax path — always available, numerically identical."""
        if not self.use_kernels or desc.fused_with is None or x.ndim != 2:
            return False
        return isinstance(jax.lax.axis_size(self.axis_name), int)

    def _streamed_ok(self, desc: TransferDescriptor, x) -> bool:
        """Streamed-MEM preconditions: kernels enabled, 2-D payload, a
        declared consumer matmul, and the active plan marks this transfer
        streamed (the planner's double-buffered MEM verdict).  Anything
        else takes the serial memory round-trip — always available,
        numerically identical."""
        if not self.use_kernels or desc.fused_with is None or x.ndim != 2:
            return False
        plan = self.plan()
        if plan is None:
            return False
        return (plan.streamed(desc.name) or
                plan.streamed(base_transfer_name(desc.name)))

    def _fused_site(self, desc: TransferDescriptor, x, hint
                    ) -> Tuple[CommMode, jax.Array, int, isa.DmaInstruction]:
        """Shared issue-site prolog of the two FUSED_RING methods:
        resolve the mode, build the write-channel control beat — MEM
        encodes user 0, a P2P ring hop the user=1 unicast degeneracy, an
        MCAST verdict the full ring's destination list — and fold the C3
        fence in."""
        mode = self.resolve_mode(desc, hint)
        nbytes = self._nbytes(x)
        word = desc.word_bytes or x.dtype.itemsize
        if mode is CommMode.MEM:
            dests: Tuple[int, ...] = ()
        elif mode is CommMode.P2P:
            dests = (1,)
        else:
            n = jax.lax.axis_size(self.axis_name)
            dests = (tuple(range(1, n))
                     if isinstance(n, int) and n > 1 else (1,))
        req = CommRequest(max(nbytes // word, 1), word, mode, dests=dests)
        instr = isa.encode(req, isa.CH_WRITE)
        if desc.sync:
            x = self._fence(x, mode)
        return mode, x, nbytes, instr

    def gather_matmul(self, x: jax.Array, w: jax.Array,
                      desc: TransferDescriptor,
                      hint: Optional[CommMode] = None) -> jax.Array:
        """Fused all-gather + matmul: ``concat_ring(x) @ w`` where ``x``
        is this rank's (m, k) row shard and ``w`` the (k, n) replicated
        operand; returns (P*m, n) on every rank.

        FUSED_RING dispatch: when the active plan prices the transfer to
        P2P (the overlap planner's fused ring chain) and
        ``desc.fused_with`` names the consumer matmul, the ring
        all-gather-matmul kernel multiplies chunk k while chunk k+1
        streams to the right neighbour — the paper's burst-pipelined
        overlap on the MXU.  The unfused lax path (all_gather, then dot)
        is the always-available fallback — it also serves a P2P or MCAST
        verdict whose preconditions are unmet (issued serially under the
        resolved mode, ``fused=False``).

        A MEM verdict the plan marks *streamed* (``CommPlan.streamed``)
        dispatches the double-buffered stream instead of the serial
        round-trip: the gather still rides the memory path, but the
        gathered operand feeds the matmul in row blocks with block i+1's
        IDMA behind block i's compute (``kernels.streamed_gather``, the
        C5 schedule) — issued MEM, recorded ``fused=True``.  Plain MEM
        is charged the serial memory round-trip as before."""
        assert self.axis_name is not None, "gather_matmul needs a stage axis"
        mode, x, nbytes, instr = self._fused_site(desc, x, hint)
        if mode is CommMode.P2P and self._fused_ring_ok(desc, x):
            from repro.kernels.ring_allgather_matmul import \
                ring_allgather_matmul_local

            def kernel():
                return ring_allgather_matmul_local(
                    x, w, axis_name=self.axis_name, interpret=self.interpret)

            return self._ladder(desc, "gather_matmul", mode, nbytes, [
                ("FUSED_RING", CommMode.P2P, instr.user,
                 "ring_allgather_matmul", True, kernel),
                ("P2P", CommMode.P2P, instr.user, "lax_all_gather", False,
                 lambda: self._serial_gather_matmul(x, w)),
                ("MEM", CommMode.MEM, 0, "mem_roundtrip", False,
                 lambda: self._serial_gather_matmul(x, w)),
            ])
        if mode is CommMode.MEM and self._streamed_ok(desc, x):
            from repro.kernels.streamed_gather import \
                streamed_gather_matmul_local

            def stream():
                return streamed_gather_matmul_local(
                    x, w, axis_name=self.axis_name, interpret=self.interpret)

            return self._ladder(desc, "gather_matmul", mode, nbytes, [
                ("FUSED_RING", CommMode.MEM, 0,
                 "streamed_gather_matmul", True, stream),
                ("MEM", CommMode.MEM, 0, "mem_roundtrip", False,
                 lambda: self._serial_gather_matmul(x, w)),
            ])
        self._log(desc, "gather_matmul", mode, mode, instr.user, nbytes,
                  "mem_roundtrip" if mode is CommMode.MEM
                  else "lax_all_gather")
        return self._serial_gather_matmul(x, w)

    def _serial_gather_matmul(self, x, w):
        full = jax.lax.all_gather(x, self.axis_name, axis=0, tiled=True)
        out_dtype = jnp.promote_types(x.dtype, w.dtype)
        return jnp.dot(full, w,
                       preferred_element_type=jnp.float32).astype(out_dtype)

    def matmul_reduce_scatter(self, x: jax.Array, w: jax.Array,
                              desc: TransferDescriptor,
                              hint: Optional[CommMode] = None) -> jax.Array:
        """Fused matmul + ring reduce-scatter:
        ``reduce_scatter(x @ w, axis)`` where every rank holds ``x``
        (m, k_p) — a column shard of the contraction — and ``w`` (k_p, n);
        returns this rank's fully-reduced (m/P, n) in f32.

        Unlike a plain reduction (pinned MEM: the NoC cannot combine in
        flight), the fused ring combines the partial sums *in the
        accelerator* at every hop, so a P2P verdict dispatches the ring
        reduce-scatter-matmul kernel (FUSED_RING).  Fallback: dot then
        ``psum_scatter`` — same numbers, serial comm under the resolved
        mode."""
        assert self.axis_name is not None, \
            "matmul_reduce_scatter needs a stage axis"
        mode, x, nbytes, instr = self._fused_site(desc, x, hint)
        n = jax.lax.axis_size(self.axis_name)
        divisible = isinstance(n, int) and x.shape[0] % n == 0
        if mode is CommMode.P2P and divisible and \
                self._fused_ring_ok(desc, x):
            from repro.kernels.ring_reducescatter_matmul import \
                ring_reducescatter_matmul_local

            def kernel():
                return ring_reducescatter_matmul_local(
                    x, w, axis_name=self.axis_name, interpret=self.interpret)

            return self._ladder(desc, "reduce_scatter", mode, nbytes, [
                ("FUSED_RING", CommMode.P2P, instr.user,
                 "ring_reducescatter_matmul", True, kernel),
                ("P2P", CommMode.P2P, instr.user, "lax_psum_scatter", False,
                 lambda: self._serial_matmul_reduce_scatter(x, w)),
                ("MEM", CommMode.MEM, 0, "mem_roundtrip", False,
                 lambda: self._serial_matmul_reduce_scatter(x, w)),
            ])
        self._log(desc, "reduce_scatter", mode, mode, instr.user, nbytes,
                  "mem_roundtrip" if mode is CommMode.MEM
                  else "lax_psum_scatter")
        return self._serial_matmul_reduce_scatter(x, w)

    def _serial_matmul_reduce_scatter(self, x, w):
        part = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(part, self.axis_name,
                                    scatter_dimension=0, tiled=True)

    # -- pipeline helpers -----------------------------------------------------
    def forward_to_next(self, x: jax.Array,
                        desc: Optional[TransferDescriptor] = None
                        ) -> jax.Array:
        """GPipe-style stage hand-off: every stage forwards its activation
        to the next (the paper's NN example).  The shift always happens —
        a MEM verdict charges it to the memory round-trip (the producer
        writes, the successor reads back), it does not drop the
        hand-off."""
        assert self.axis_name is not None, "forward_to_next needs a stage axis"
        desc = desc or TransferDescriptor("stage_activation", pull=True)
        mode = self.resolve_mode(desc, CommMode.P2P)
        nbytes = self._nbytes(x)
        mem = mode is CommMode.MEM
        if desc.sync:
            x = self._fence(x, mode)
        self._log(desc, "read", mode,
                  CommMode.MEM if mem else CommMode.P2P, 0 if mem else 1,
                  nbytes, "mem_roundtrip" if mem else "ppermute")
        return P2P.pipeline_stage_forward(x, self.axis_name)

    # ----------------------------------------------------------- internals ----
    def _mem(self, x, desc: TransferDescriptor):
        """The MEM path: a resharding constraint along the descriptor's
        own logical axes (a weight or KV descriptor names weight/KV axes —
        never an activation-shaped guess).  A descriptor with no axes is
        a placement no-op."""
        if not desc.axes:
            return x
        return logical_constraint(x, tuple(desc.axes)[: x.ndim])

    def _kernel_ok(self, x, ranks: Sequence[int], src: int) -> bool:
        """Pallas multicast-stream constraints: kernels enabled, 2-D
        payload with rows splittable into >= 2 chunks, and the
        destination set (excluding the source) covers the whole ring —
        the stream forwards hop-by-hop through EVERY member, so a rank
        the descriptor excluded must not be on the path."""
        if not self.use_kernels or x.ndim != 2:
            return False
        n = jax.lax.axis_size(self.axis_name)
        if not isinstance(n, int):
            return False
        covers = len(set(ranks) - {src}) >= n - 1
        return covers and self._kernel_chunks(x) is not None

    def _kernel_chunks(self, x) -> Optional[int]:
        for c in (4, 2):
            if x.shape[0] % c == 0:
                return c
        return None


def socket_for_axis(axis_name: Optional[str],
                    plan: Optional[CommPlan] = None, *,
                    use_kernels: bool = False,
                    interpret=None,
                    retry: Optional[RetryPolicy] = None,
                    fence_timeout_s: float = 0.0) -> AcceleratorSocket:
    """A lightweight socket bound to a mesh axis (no LUT): the form model
    code uses inside shard_map bodies.  The plan defaults to the ambient
    ``use_rules`` context at issue time.  ``use_kernels``/``interpret``
    forward to the Pallas fast paths (multicast stream, FUSED_RING);
    ``retry``/``fence_timeout_s`` arm the degradation ladder and the
    fence stall watchdog (both off by default)."""
    return AcceleratorSocket(None, plan, axis_name=axis_name,
                             use_kernels=use_kernels, interpret=interpret,
                             retry=retry, fence_timeout_s=fence_timeout_s)


_AMBIENT = AcceleratorSocket()


def mem_write(x, name: str, axes: Sequence[Optional[str]],
              site: Optional[str] = None):
    """Issue a memory-path write on the ambient (axis-less) socket: the
    descriptor-based replacement for a bare ``logical_constraint`` at a
    transfer site — the DMA-to-memory half of the dispatch matrix, still
    logged per site."""
    return _AMBIENT.write(x, TransferDescriptor(name, axes=tuple(axes),
                                                site=site))
