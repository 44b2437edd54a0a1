"""Tier-1 tests of the serving engine's in-program spans and counters
(``runtime/tracing.py``): one well-nested tree per step, one admission
span per admitted request, counters equal to what ``step()`` returns,
request times read off the span boundaries, nothing recorded without a
recorder, and the named step programs."""

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.runtime import tracing
from repro.runtime.engine import ServeEngine
from repro.runtime.tracing import EngineTrace

CHILDREN = {"engine.step": {"engine.admit", "engine.upload", "engine.decode",
                            "engine.sample", "engine.bookkeep"},
            "engine.admit": {"engine.prefill", "engine.first_token"}}


def _engine(trace=None, n_slots=2):
    return ServeEngine(get_reduced("qwen3-4b"), prompt_len=8,
                       max_new_tokens=4, n_slots=n_slots, block_size=4,
                       trace=trace)


def _serve(eng, arrivals=(0, 0, 0, 2), max_new=(4, 1, 3, 4)):
    prompts = np.asarray(jax.random.randint(
        jax.random.key(3), (len(arrivals), 8), 0, eng.cfg.vocab_size),
        np.int32)
    for i, (arr, n) in enumerate(zip(arrivals, max_new)):
        eng.submit(prompts[i], n, rid=i, arrival_step=arr)
    infos = []
    while eng.pending or eng.n_active:
        if not eng.n_active and eng.pending[0].arrival_step > eng.step_idx:
            eng.step_idx = eng.pending[0].arrival_step
        infos.append(eng.step())
    return infos


@pytest.fixture(scope="module")
def served():
    tr = EngineTrace()
    eng = _engine(tr)
    infos = _serve(eng)
    return eng, tr.export(), infos


def test_every_span_lies_inside_its_parent(served):
    _, ex, _ = served
    by_sid = {s.sid: s for s in ex["spans"]}
    assert ex["dropped"] == 0
    for s in ex["spans"]:
        assert s.t0_ns <= s.t1_ns
        if s.parent is None:
            assert s.name == "engine.step"
            continue
        p = by_sid[s.parent]
        assert p.t0_ns <= s.t0_ns and s.t1_ns <= p.t1_ns
        assert s.name in CHILDREN[p.name]


def test_one_step_span_per_step_and_counters_equal_step(served):
    _, ex, infos = served
    roots = [s for s in ex["spans"] if s.parent is None]
    assert len(roots) == len(ex["steps"]) == len(infos)
    assert [st["span"] for st in ex["steps"]] == [s.sid for s in roots]
    for st, info in zip(ex["steps"], infos):
        assert (st["admitted"], st["active"], st["n_slots"]) == \
            (info["admitted"], info["active"], 2)
    # a step with a batch to decode has each decode-side span exactly once
    for st, root in zip(ex["steps"], roots):
        kids = [s.name for s in ex["spans"] if s.parent == root.sid]
        decode_side = [k for k in kids if k != "engine.admit"]
        assert kids.count("engine.admit") == st["admitted"]
        assert decode_side == (["engine.upload", "engine.decode",
                                "engine.sample", "engine.bookkeep"]
                               if st["active"] else [])


def test_kv_block_counters_equal_what_the_tables_imply():
    """``kv_blocks_read`` is ``ceil((pos + 1) / block_size)`` summed over
    the slots the decode step ran for (those with a live table row), and
    ``kv_blocks_span`` the whole table, on every step."""
    tr = EngineTrace()
    eng = _engine(tr)
    inputs = []
    decode = eng._decode

    def spy(params, tokens, pos, pools, tables):
        inputs.append((np.array(pos), np.array(tables)))
        return decode(params, tokens, pos, pools, tables)

    eng._decode = spy
    infos = _serve(eng)
    steps = tr.export()["steps"]
    decoded = iter(inputs)
    bs, mb = eng.layout.block_size, eng.layout.max_blocks
    for st, info in zip(steps, infos):
        assert st["kv_blocks_span"] == 2 * mb
        if not info["active"]:
            assert st["kv_blocks_read"] == 0
            continue
        pos, tables = next(decoded)
        live = tables[:, 0] != 0
        assert live.sum() == info["active"]
        want = int(np.sum((pos[live] + 1 + bs - 1) // bs))
        # the table holds every block the step reads, and no fewer
        assert want <= int(np.sum(tables[live] != 0))
        assert st["kv_blocks_read"] == want
    assert next(decoded, None) is None
    assert sum(st["kv_blocks_read"] for st in steps) > 0


def test_slot_state_bytes_counts_the_active_slots_recurrent_state(served):
    """``slot_state_bytes``: the float32 Mamba ``h`` (d_inner x N) and conv
    tail ((d_conv - 1) x d_inner) of every Mamba layer, for each active
    slot (Jamba); 0 where every leaf is paged KV (qwen3)."""
    _, ex, _ = served
    assert all(st["slot_state_bytes"] == 0 for st in ex["steps"])
    cfg = get_reduced("jamba2-mini")
    tr = EngineTrace()
    infos = _serve(ServeEngine(cfg, prompt_len=8, max_new_tokens=4,
                               n_slots=2, block_size=4, trace=tr))
    di = cfg.ssm.expand * cfg.d_model
    per_slot = cfg.block_kinds().count("mamba") * 4 * (
        di * cfg.ssm.state_dim + (cfg.ssm.conv_dim - 1) * di)
    steps = tr.export()["steps"]
    assert [st["slot_state_bytes"] for st in steps] == \
        [info["active"] * per_slot for info in infos]
    assert any(st["slot_state_bytes"] for st in steps)


def test_one_admit_span_per_admitted_request(served):
    eng, ex, infos = served
    admits = [s for s in ex["spans"] if s.name == "engine.admit"]
    assert sorted(s.rid for s in admits) == [0, 1, 2, 3]
    assert sum(i["admitted"] for i in infos) == len(admits)
    kids = {}
    for s in ex["spans"]:
        kids.setdefault(s.parent, []).append(s.name)
    for a in admits:
        assert kids[a.sid] == ["engine.prefill", "engine.first_token"]


def test_request_times_are_span_boundaries(served):
    eng, ex, _ = served
    by_sid = {s.sid: s for s in ex["spans"]}
    first_token = {by_sid[s.parent].rid: s for s in ex["spans"]
                   if s.name == "engine.first_token"}
    step_starts = {s.t0_ns for s in ex["spans"] if s.name == "engine.step"}
    token_reads = {s.t0_ns for s in ex["spans"]
                   if s.name == "engine.bookkeep"}
    for r in eng.completed:
        assert r.t_admitted == first_token[r.rid].t1_ns * 1e-9
        assert any(r.t_visible == t * 1e-9 for t in step_starts)
        if r.max_new_tokens == 1:
            assert r.t_done == r.t_admitted
        else:
            assert any(r.t_done == t * 1e-9 for t in token_reads)
        assert r.t_visible <= r.t_admitted <= r.t_done


def test_nothing_recorded_without_a_recorder(monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(EngineTrace, "open",
                        lambda *a, **k: pytest.fail("recorded without one"))
    eng = _engine()
    infos = _serve(eng, arrivals=(0, 1), max_new=(3, 2))
    assert eng.trace is None and made == []
    assert sum(i["admitted"] for i in infos) == 2
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}


def test_step_programs_are_named(served):
    eng, _, _ = served
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}
    n = eng.layout.n_slots
    text = eng._decode.lower(eng.params, np.zeros((n, 1), np.int32),
                             np.zeros((n,), np.int32), eng.pools,
                             eng.tables).as_text()
    assert "module @jit_engine_decode" in text
    prompt = np.zeros((1, 8), np.int32)
    assert "module @jit_engine_prefill" in \
        eng._prefill.lower(eng.params, prompt).as_text()
    # lowering traces nothing new into the engine's own count
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}


def test_the_bound_drops_the_oldest_and_counts_them():
    tr = EngineTrace(capacity=4)
    for i in range(3):
        tr.open("engine.step", 10 * i)
        tr.open("engine.decode", 10 * i + 1)
        tr.close(10 * i + 2)
        tr.count(active=i)
        tr.close(10 * i + 3)
    ex = tr.export()
    assert ex["dropped"] == 2 and len(ex["spans"]) == 4
    assert ex["spans"][0] == tracing.Span(3, "engine.decode", 11, 12, 2,
                                          None)
    assert [s["active"] for s in ex["steps"]] == [0, 1, 2]
    assert ex["steps"][-1]["t1_ns"] == 23
