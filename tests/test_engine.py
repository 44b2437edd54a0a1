"""Tier-1 tests for the continuous-batching serving engine: admission /
eviction accounting over a scripted arrival trace, the one-trace-per-
function contract across admissions / remaps, epoch-scoped issue-log
keys, the recorded serve-path downgrades, and token equality against a
per-request contiguous reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import socket as SOCK
from repro.core.comm import CommMode, CommPlan
from repro.models import transformer as T
from repro.runtime import kv_blocks as KB
from repro.runtime import serve as RS
from repro.runtime.engine import ServeEngine, ServeMetrics, poisson_trace


def _engine(arch="qwen3-4b", **kw):
    kw.setdefault("prompt_len", 8)
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("n_slots", 2)
    kw.setdefault("block_size", 4)
    return ServeEngine(get_reduced(arch), **kw)


def _prompts(cfg, n, S=8, seed=11):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n, S), 0,
                                         cfg.vocab_size), np.int32)


# ------------------------------------------------- admission / eviction ----

def test_scripted_trace_admission_and_eviction():
    SOCK.reset_issue_log()
    eng = _engine()
    prompts = _prompts(eng.cfg, 4)
    for i, arr in enumerate((0, 0, 0, 3)):
        eng.submit(prompts[i], arrival_step=arr, rid=i)

    seen_active = []
    while eng.pending or eng.n_active:
        stats = eng.step()
        seen_active.append(stats["active"])
        # slots and blocks stay within provisioning at every step
        assert eng.n_active <= 2
        owned = sum(len(r.blocks) for r in eng._slot_req if r is not None)
        assert eng.allocator.n_used == owned

    # two slots, three day-0 arrivals: the third waited for an eviction
    assert max(seen_active) == 2
    assert len(eng.completed) == 4 and not eng.pending
    assert all(len(r.generated) == 4 and r.done for r in eng.completed)
    assert eng.allocator.n_used == 0          # every block came back
    assert sorted(eng._free_slots) == [0, 1]
    # one trace per jitted function for the whole serve
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}


def test_admission_gate_defers_when_no_slot():
    eng = _engine(n_slots=1)
    prompts = _prompts(eng.cfg, 2)
    eng.submit(prompts[0], arrival_step=0, rid=0)
    eng.submit(prompts[1], arrival_step=0, rid=1)
    stats = eng.step()
    assert stats["admitted"] == 1 and len(eng.pending) == 1
    while eng.pending or eng.n_active:
        eng.step()
    assert [r.rid for r in eng.completed] == [0, 1]


def test_submit_validates_against_the_layout():
    eng = _engine()
    with pytest.raises(ValueError):
        eng.submit(np.zeros(5, np.int32))                 # wrong prompt len
    with pytest.raises(ValueError):
        eng.submit(np.zeros(8, np.int32), max_new_tokens=99)


def test_max_new_tokens_one_is_served_by_the_prefill_token():
    eng = _engine()
    eng.submit(_prompts(eng.cfg, 1)[0], max_new_tokens=1)
    while eng.pending or eng.n_active:
        eng.step()
    (req,) = eng.completed
    assert len(req.generated) == 1 and eng.allocator.n_used == 0


def test_run_metrics_sanity():
    eng = _engine()
    trace = poisson_trace(5, rate=0.7, prompt_len=8, vocab=eng.cfg.vocab_size,
                          max_new_tokens=4, seed=5)
    metrics = eng.run(trace)
    assert isinstance(metrics, ServeMetrics)
    assert metrics.n_requests == 5
    assert metrics.total_new_tokens == sum(len(r.generated)
                                           for r in eng.completed) == 20
    assert metrics.tokens_per_s > 0
    assert 0 <= metrics.p50_latency_s <= metrics.p99_latency_s
    s = metrics.summary()
    assert s["n_requests"] == 5 and s["total_new_tokens"] == 20


def test_poisson_trace_is_deterministic():
    a = poisson_trace(4, rate=0.5, prompt_len=8, vocab=64, max_new_tokens=2,
                      seed=9)
    b = poisson_trace(4, rate=0.5, prompt_len=8, vocab=64, max_new_tokens=2,
                      seed=9)
    assert [r.arrival_step for r in a] == [r.arrival_step for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_decode_donates_the_pools_and_traces_once():
    eng = _engine()
    prompts = _prompts(eng.cfg, 3, seed=51)
    for i, arr in enumerate((0, 0, 1)):
        eng.submit(prompts[i], arrival_step=arr, rid=i)
    handed = []
    decode = eng._decode

    def spy(params, tokens, pos, pools, tables):
        handed.append(pools)
        return decode(params, tokens, pos, pools, tables)

    eng._decode = spy
    while eng.pending or eng.n_active:
        eng.step()
        # the step wrote into the pools it was handed, which are gone;
        # the engine holds the ones it returned
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed[-1]))
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng.pools))
    assert len(handed) > 3 and eng.trace_counts["decode"] == 1
    assert len(eng.completed) == 3


def test_engine_with_a_mesh_binds_the_gather_scatter_step(monkeypatch):
    """The kernel does not partition under the sequence-sharded serve
    rules, so an engine built with a mesh keeps the gather/scatter step;
    without one it reads in place.  Both serve the same tokens."""
    gathers = []
    gather = KB.gather_caches
    monkeypatch.setattr(KB, "gather_caches",
                        lambda *a: gathers.append(1) or gather(*a))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    served = {}
    for name, m in (("mesh", mesh), ("none", None)):
        eng = _engine(mesh=m)
        for i, p in enumerate(_prompts(eng.cfg, 2, seed=61)):
            eng.submit(p, rid=i)
        while eng.pending or eng.n_active:
            eng.step()
        served[name] = {r.rid: r.generated for r in eng.completed}
        assert eng._in_place is (m is None)
        # traced once each: one gather with the mesh, none without
        assert len(gathers) == 1
    assert served["mesh"] == served["none"]


# ------------------------------------------ tokens vs contiguous decode ----

def test_engine_tokens_match_contiguous_reference():
    eng = _engine()
    prompts = _prompts(eng.cfg, 3, seed=21)
    for i, arr in enumerate((0, 1, 2)):      # staggered: batching overlaps
        eng.submit(prompts[i], arrival_step=arr, rid=i)
    while eng.pending or eng.n_active:
        eng.step()
    got = {r.rid: list(r.generated) for r in eng.completed}

    # per-request reference: contiguous prefill -> grow -> batched decode
    prefill = jax.jit(RS.make_prefill_step(eng.cfg, eng.flags))
    decode = jax.jit(RS.make_batched_decode_step(eng.cfg, eng.flags))
    for i in range(3):
        logits, caches = prefill(eng.params, prompts[i][None, :])
        caches = RS.grow_caches(eng.cfg, caches, 8, 4)
        toks = [int(np.asarray(jnp.argmax(logits[0, -1])))]
        for j in range(3):
            logits, caches = decode(eng.params,
                                    jnp.asarray([[toks[-1]]], jnp.int32),
                                    jnp.asarray([8 + j], jnp.int32), caches)
            toks.append(int(np.asarray(jnp.argmax(logits[0, -1]))))
        assert got[i] == toks, f"request {i} diverged from reference"


# ----------------------------------- issue-log epochs + recorded modes ----

def test_issue_log_is_epoch_scoped():
    SOCK.reset_issue_log()
    eng = _engine()
    eng.submit(_prompts(eng.cfg, 1)[0])
    while eng.pending or eng.n_active:
        eng.step()
    modes = SOCK.issued_modes()
    # regression (satellite 3): the admission burst and the steady decode
    # are distinct audit keys — an unscoped log would collapse each site
    # to last-write-wins and the prefill-phase record would vanish
    assert "engine.kv_prefix@prefill" in modes
    assert "prefill.weights_gather@prefill" in modes
    assert "decode.weights_gather@decode" in modes
    kv = modes["engine.kv_prefix@prefill"]
    # no live stage axis inside the engine's jit domain: the multicast
    # degrades to the recorded MEM path, reason attached
    assert kv["issued"] == "MEM" and kv["degraded_reason"]


def test_decode_downgrade_is_recorded_not_mutating():
    cfg = get_reduced("dbrx-132b")
    flags = T.RunFlags(remat="none", moe_mode="mcast")
    plan = CommPlan({"moe_dispatch": CommMode.MCAST,
                     "weights": CommMode.MEM})
    SOCK.reset_issue_log()
    new_flags, new_plan = RS._decode_downgrades(cfg, flags, plan)
    # regression (satellite 1): dataclasses.replace semantics — the caller's
    # flags object is untouched and every other field carries over
    assert flags.moe_mode == "mcast"
    assert new_flags.moe_mode == "mem"
    assert dataclasses.asdict(new_flags) == {
        **dataclasses.asdict(flags), "moe_mode": "mem"}
    assert plan.mode("moe_dispatch") is CommMode.MCAST   # plan not mutated
    assert new_plan.mode("moe_dispatch") is CommMode.MEM
    # the downgrade lands at the descriptor's canonical site so the
    # coverage gate resolves it through the fused chain's declaration
    rec = [r for r in SOCK.issued_records()
           if r.site == "moe.dispatch"][-1]
    assert rec.issued == "MEM" and rec.degraded_reason == "decode_no_seq_dim"
    assert rec.impl == "decode_downgrade"


# -------------------------------------------------- remap / re-plan -------

def test_remap_consumer_mid_serve_never_retraces():
    eng = _engine(consumers=("decode1", "decode2"))
    prompts = _prompts(eng.cfg, 3, seed=31)
    eng.submit(prompts[0], rid=0)
    eng.step()
    counts_before = dict(eng.trace_counts)
    eng.remap_consumer("decode2", 5)
    assert eng.registry.rank_of("decode2") == 5
    assert [int(r) for r in np.asarray(eng.consumer_ranks())][-1] == 5
    # later admissions and decodes reuse the existing traces
    eng.submit(prompts[1], rid=1)
    eng.submit(prompts[2], rid=2)
    while eng.pending or eng.n_active:
        eng.step()
    assert eng.trace_counts == counts_before == \
        {"prefill": 1, "decode": 1, "admit": 1}


def test_replan_for_mesh_rebinds_and_keeps_serving():
    eng = _engine()
    prompts = _prompts(eng.cfg, 2, seed=41)
    eng.submit(prompts[0], rid=0)
    eng.step()
    old_plan = eng.plan
    flips = eng.replan_for_mesh({"x": 4, "stage": 2})
    assert isinstance(flips, list)
    assert eng.plan is not old_plan
    # re-mesh is a re-plan: the rebound step may trace once more, and
    # serving continues over the same pools / tables / scheduler state
    eng.submit(prompts[1], rid=1)
    while eng.pending or eng.n_active:
        eng.step()
    assert len(eng.completed) == 2
    assert all(len(r.generated) == 4 for r in eng.completed)
    assert eng.allocator.n_used == 0
