"""Continuous-batching serving engine with plan-driven KV movement.

One :class:`ServeEngine` owns a paged block cache
(:mod:`repro.runtime.kv_blocks`) and runs a *single* continuously
batched decode step over every in-flight request: admissions land in
free slots mid-stream, completions free their blocks for the next
arrival, and the step itself never retraces — per-request depth rides
the ``(n_slots,)`` position vector and the ``(n_slots, max_blocks)``
block table, both plain device arrays.

The prefill -> decode hand-off is a *communication spine* transfer, not
an implementation detail: each admitted request's cache prefix issues
through :class:`~repro.core.socket.AcceleratorSocket` from the
``engine.kv_prefix`` :class:`~repro.core.comm.TransferDescriptor` — a
one-burst multicast from the ``prefill`` stage to every registered
decode consumer (the paper's Fig. 1(c) dataflow), priced by
:func:`~repro.core.planner.kv_prefix_transfer_spec` against the cache
shape x the consumer count.  On a topology with no live stage axis the
socket degrades the write to the MEM path and records the degradation
reason — delivery and accounting both stay audit-visible in the issue
log, scoped per engine phase by :func:`~repro.core.socket.issue_epoch`
(``engine.kv_prefix@prefill`` vs ``decode.weights_gather@decode``), so
``issued_modes()`` distinguishes the admission burst from the steady
decode even though both trace once.

Consumers are *virtualized*: :meth:`ServeEngine.remap_consumer` is a
:class:`~repro.core.socket.StageRegistry` LUT update, and the live-axis
writer from :meth:`ServeEngine.make_stage_kv_writer` takes the consumer
ranks as traced values — retargeting a decode stage mid-serve never
retraces (``trace_counts`` stays flat; tier-1 asserted).  A mesh change
is a *re-plan*: :meth:`ServeEngine.replan_for_mesh` re-prices the
serve-step specs (including ``kv_prefix``) on the survivor topology via
:func:`repro.runtime.fault.replan_for_mesh` and rebinds the step
factories to the new plan.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.comm import TransferDescriptor
from repro.core.planner import CommPlanner, step_transfer_specs
from repro.core.socket import AcceleratorSocket, StageRegistry, issue_epoch
from repro.models import transformer as T
from repro.runtime import kv_blocks as KB
from repro.runtime import serve as RS

KV_PREFIX_SITE = "engine.kv_prefix"


# ---------------------------------------------------------------- requests ----

@dataclasses.dataclass
class Request:
    """One serving request and its engine-side lifecycle state."""
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32 token ids
    max_new_tokens: int
    arrival_step: int = 0              # engine step index it becomes visible
    # --- engine-managed state ---
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_visible: float = 0.0             # wall clock when arrival_step opened
    t_admitted: float = 0.0
    t_done: float = 0.0

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_visible


@dataclasses.dataclass(frozen=True)
class ServeMetrics:
    """What the ``serve_load`` benchmark row reports."""
    n_requests: int
    total_new_tokens: int
    steps: int
    wall_s: float
    tokens_per_s: float
    p50_latency_s: float
    p99_latency_s: float
    latencies_s: Tuple[float, ...] = ()

    def summary(self) -> Dict[str, float]:
        return {"n_requests": self.n_requests,
                "total_new_tokens": self.total_new_tokens,
                "steps": self.steps, "wall_s": round(self.wall_s, 6),
                "tokens_per_s": round(self.tokens_per_s, 3),
                "p50_latency_s": round(self.p50_latency_s, 6),
                "p99_latency_s": round(self.p99_latency_s, 6)}


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


def poisson_trace(n_requests: int, rate: float, prompt_len: int, vocab: int,
                  max_new_tokens: int, *, seed: int = 0) -> List[Request]:
    """Deterministic Poisson arrival trace: inter-arrival gaps drawn from
    ``random.Random(seed).expovariate(rate)`` in units of *decode steps*
    (the engine's scheduling clock), prompts uniform over the vocab.  The
    same seed always yields the same trace — the serve_load benchmark and
    CI gate depend on that."""
    rng = random.Random(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += rng.expovariate(rate)
        prompt = np.asarray([rng.randrange(vocab) for _ in range(prompt_len)],
                            np.int32)
        out.append(Request(rid=i, prompt=prompt,
                           max_new_tokens=max_new_tokens,
                           arrival_step=int(t)))
    return out


# ------------------------------------------------------------------ engine ----

class ServeEngine:
    """Continuous-batching serving over a paged KV cache.

    ``submit`` enqueues requests; ``step`` admits as many as fit (free
    slot + free blocks for the full depth), runs one batched decode over
    every active slot, and evicts completions; ``run`` drives a whole
    arrival trace and returns :class:`ServeMetrics`.

    Tracing contract: exactly one trace per jitted function for the
    engine's lifetime (``trace_counts`` is tier-1 asserted) — admission,
    block growth, eviction and consumer remaps are all host-side table /
    LUT updates.
    """

    def __init__(self, cfg: ArchConfig, *, prompt_len: int,
                 max_new_tokens: int, n_slots: int = 4, block_size: int = 16,
                 consumers: Sequence[str] = ("decode1", "decode2"),
                 flags: Optional[T.RunFlags] = None, mesh=None, rules=None,
                 plan=None, params=None, seed: int = 0,
                 param_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                 mesh_axes: Optional[Dict[str, int]] = None,
                 planner: Optional[CommPlanner] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self.flags = flags or T.RunFlags(param_dtype=param_dtype,
                                         cache_dtype=cache_dtype,
                                         remat="none")
        self.layout = KB.paged_layout(cfg, n_slots=n_slots,
                                      prompt_len=prompt_len,
                                      max_new_tokens=max_new_tokens,
                                      block_size=block_size,
                                      dtype=self.flags.cache_dtype)
        self.allocator = KB.BlockAllocator(1 + self.layout.capacity_blocks)
        self.pools = KB.make_pools(self.layout)
        self.tables = KB.null_table(self.layout)

        # --- the communication spine: registry, plan, socket, descriptor ---
        self.registry = StageRegistry("stage")
        self.registry.register("prefill", 0)
        for i, name in enumerate(consumers):
            self.registry.register(name, i + 1)
        self.consumers = tuple(consumers)
        self._mesh_axes = dict(mesh_axes or {})
        self.shape = ShapeConfig(f"serve_{prompt_len}", prompt_len, n_slots,
                                 "decode")
        if plan is None:
            planner = planner or CommPlanner()
            plan, self.plan_decisions = planner.plan_with_decisions(
                step_transfer_specs(cfg, self.shape, self._mesh_axes,
                                    kv_consumers=len(self.consumers)))
        else:
            self.plan_decisions = []
        self.plan = plan
        self.socket = AcceleratorSocket(self.registry, plan)
        # the engine's own jit domain has no live stage axis (the GSPMD
        # mesh, if any, is not a pipeline): null the axis the constructor
        # inherited from the registry so every kv_prefix write takes the
        # recorded MEM degradation instead of tracing a dead collective.
        # make_stage_kv_writer rebinds the axis for shard_map callers.
        self.socket.axis_name = None
        # literal site label: commcheck's extractor admits it into the
        # --against-artifact coverage universe (KV_PREFIX_SITE mirrors it)
        self.kv_desc = TransferDescriptor(
            "kv_prefix", source="prefill", dests=self.consumers, sync=True,
            site="engine.kv_prefix")

        # --- model state + jitted step functions (one trace each) ---
        if params is None:
            shardings = None if mesh is None else RS.serve_shardings(
                cfg, mesh, n_slots, prompt_len + max_new_tokens, rules,
                self.flags.param_dtype)[0]
            params = T.init_params_on_device(jax.random.key(seed), cfg,
                                             self.flags.param_dtype,
                                             shardings)
        self.params = params
        self.trace_counts: Dict[str, int] = {"prefill": 0, "decode": 0,
                                             "admit": 0}
        self._prefill = jax.jit(self._counted(
            "prefill", RS.make_prefill_step(cfg, self.flags, mesh, rules,
                                            self.plan)))
        self._bind_decode()
        self._admit = jax.jit(self._counted("admit", self._admit_fn))

        # --- scheduler state ---
        self._slot_req: List[Optional[Request]] = [None] * n_slots
        self._free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        self._tokens = np.zeros((n_slots, 1), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        self.pending: List[Request] = []
        self.completed: List[Request] = []
        self.step_idx = 0

    # ------------------------------------------------------------ plumbing ----
    def _counted(self, key: str, fn: Callable) -> Callable:
        def wrapped(*a):
            # runs at trace time only: jit caches the traced computation,
            # so this counter measures retraces, not calls
            self.trace_counts[key] += 1
            return fn(*a)
        return wrapped

    def _bind_decode(self):
        # bind under the decode epoch: the step factory's downgrade
        # records (the MoE dispatch chain's decode_no_seq_dim demotion)
        # key as "moe.dispatch@decode" in the artifact's issue summary
        with issue_epoch("decode"):
            self._decode = jax.jit(self._counted(
                "decode", RS.make_paged_decode_step(self.cfg, self.flags,
                                                    self.layout, self.mesh,
                                                    self.rules, self.plan)))

    def _admit_fn(self, pools, prefix_caches, slot, block_ids):
        """Traced once: multicast one request's prefill caches through the
        socket (the plan's kv_prefix verdict; degraded to recorded MEM
        with no stage axis), then land them in the block pools."""
        prefix_caches = jax.tree.map(
            lambda c: self.socket.write(c, self.kv_desc), prefix_caches)
        return KB.write_prefix(self.layout, pools, prefix_caches, slot,
                               block_ids)

    def make_stage_kv_writer(self, axis_name: str) -> Callable:
        """A kv_prefix writer for callers with a *live* stage axis (use
        inside ``shard_map`` over ``axis_name``): ``writer(leaf, ranks)``
        multicasts ``leaf`` from the prefill rank to the traced consumer
        ``ranks`` vector under the same plan + descriptor the engine
        accounts with.  Traced ranks come from :meth:`consumer_ranks` —
        a later :meth:`remap_consumer` retargets without retracing."""
        sock = AcceleratorSocket(self.registry, self.plan,
                                 axis_name=axis_name)

        def writer(leaf, ranks):
            return sock.write(leaf, self.kv_desc,
                              producer=0, dests=list(ranks))
        return writer

    def consumer_ranks(self) -> jnp.ndarray:
        """The consumers' current LUT ranks as a traced (n,) int32 vector."""
        return jnp.asarray([self.registry.rank_of(n) for n in self.consumers],
                           jnp.int32)

    def remap_consumer(self, name: str, new_rank: int) -> None:
        """Retarget a decode consumer: a LUT update, never a retrace."""
        self.registry.remap(name, new_rank)

    def replan_for_mesh(self, new_mesh_axes: Dict[str, int], *,
                        hlo_text=None, model=None):
        """Re-mesh is a re-plan: re-price the serve-step specs (kv_prefix
        included) on the survivor topology and rebind the decode step to
        the new plan.  Returns the ``plan_decision_flips`` record."""
        from repro.core.planner import plan_decision_flips, resolve_policy
        specs = step_transfer_specs(self.cfg, self.shape, new_mesh_axes,
                                    kv_consumers=len(self.consumers))
        planner = CommPlanner(model=model)
        new_plan, decisions = planner.plan_with_decisions(specs)
        flips = plan_decision_flips(self.plan, new_plan)
        self.plan, self.plan_decisions = new_plan, decisions
        self._mesh_axes = dict(new_mesh_axes)
        self.socket = AcceleratorSocket(self.registry, new_plan)
        self.socket.axis_name = None
        self._bind_decode()
        self._admit = jax.jit(self._counted("admit", self._admit_fn))
        return flips

    # ----------------------------------------------------------- scheduling ----
    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               rid: Optional[int] = None, arrival_step: int = 0) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] != self.layout.prompt_len:
            raise ValueError(f"prompt length {prompt.shape[0]} != engine "
                             f"prompt_len {self.layout.prompt_len}")
        req = Request(rid=len(self.pending) + len(self.completed) +
                      self.n_active if rid is None else rid,
                      prompt=prompt,
                      max_new_tokens=max_new_tokens or
                      self.layout.max_new_tokens,
                      arrival_step=arrival_step)
        if req.max_new_tokens > self.layout.max_new_tokens:
            raise ValueError("max_new_tokens exceeds layout provisioning")
        self.pending.append(req)
        return req

    def _admissible(self, req: Request) -> bool:
        return (req.arrival_step <= self.step_idx and
                bool(self._free_slots) and
                # conservative gate: a slot only enters if the pool can
                # carry it to full depth — admitted requests never starve
                self.allocator.n_free >= self.layout.max_blocks)

    def _admit_one(self, req: Request) -> None:
        S, bs = self.layout.prompt_len, self.layout.block_size
        n_prefix = -(-S // bs)
        n0 = max(n_prefix, self.layout.blocks_needed(S))
        req.blocks = self.allocator.alloc(n0)
        req.slot = self._free_slots.pop()
        with issue_epoch("prefill"):
            logits, caches = self._prefill(self.params, req.prompt[None, :])
            self.pools = self._admit(
                self.pools, caches, jnp.int32(req.slot),
                jnp.asarray(req.blocks[:n_prefix], jnp.int32))
        first = int(np.asarray(jnp.argmax(logits[0, -1])))
        self._slot_req[req.slot] = req
        self.tables[req.slot, :] = KB.NULL_BLOCK
        self.tables[req.slot, :len(req.blocks)] = req.blocks
        self._tokens[req.slot, 0] = first
        self._pos[req.slot] = S
        req.generated.append(first)
        req.t_admitted = time.perf_counter()

    def _evict(self, req: Request) -> None:
        self.allocator.free(req.blocks)
        self.tables[req.slot, :] = KB.NULL_BLOCK
        self._slot_req[req.slot] = None
        self._free_slots.append(req.slot)
        req.done = True
        req.t_done = time.perf_counter()
        req.blocks = []
        self.completed.append(req)

    def step(self) -> Dict[str, int]:
        """Admit what fits, decode one token for every active slot, evict
        completions.  Returns ``{"admitted", "active", "evicted"}``."""
        now = time.perf_counter()
        for r in self.pending:
            if r.arrival_step <= self.step_idx and not r.t_visible:
                r.t_visible = now
        admitted = 0
        while self.pending and self._admissible(self.pending[0]):
            self._admit_one(self.pending.pop(0))
            admitted += 1
        evicted = 0
        for req in [r for r in self._slot_req if r is not None]:
            # covers max_new_tokens == 1: the prefill token was the output
            if len(req.generated) >= req.max_new_tokens:
                self._evict(req)
                evicted += 1
        active = [r for r in self._slot_req if r is not None]
        if active:
            with issue_epoch("decode"):
                logits, self.pools = self._decode(
                    self.params, jnp.asarray(self._tokens),
                    jnp.asarray(self._pos), self.pools,
                    jnp.asarray(self.tables))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            for req in active:
                s = req.slot
                req.generated.append(int(nxt[s]))
                self._tokens[s, 0] = int(nxt[s])
                self._pos[s] += 1
                if len(req.generated) >= req.max_new_tokens:
                    self._evict(req)
                    evicted += 1
                    continue
                need = self.layout.blocks_needed(int(self._pos[s]))
                if need > len(req.blocks):
                    new = self.allocator.alloc(need - len(req.blocks))
                    self.tables[s, len(req.blocks):need] = new
                    req.blocks.extend(new)
        self.step_idx += 1
        return {"admitted": admitted, "active": len(active),
                "evicted": evicted}

    def run(self, trace: Sequence[Request]) -> ServeMetrics:
        """Drive a whole arrival trace to completion."""
        for req in sorted(trace, key=lambda r: (r.arrival_step, r.rid)):
            self.submit(req.prompt, req.max_new_tokens, rid=req.rid,
                        arrival_step=req.arrival_step)
        t0 = time.perf_counter()
        steps = 0
        while self.pending or self.n_active:
            if not self.n_active and self.pending and \
                    self.pending[0].arrival_step > self.step_idx:
                # idle gap before the next arrival: fast-forward the clock
                self.step_idx = self.pending[0].arrival_step
                continue
            self.step()
            steps += 1
        wall = time.perf_counter() - t0
        lats = sorted(r.latency_s for r in self.completed)
        total = sum(len(r.generated) for r in self.completed)
        return ServeMetrics(
            n_requests=len(self.completed), total_new_tokens=total,
            steps=steps, wall_s=wall,
            tokens_per_s=total / wall if wall > 0 else 0.0,
            p50_latency_s=_percentile(lats, 0.50),
            p99_latency_s=_percentile(lats, 0.99),
            latencies_s=tuple(lats))
