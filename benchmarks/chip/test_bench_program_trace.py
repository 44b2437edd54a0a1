"""The reduction to the engine's own programs and spans: innermost-span
attribution on made-up nested intervals, the four readers on hand-made
inputs, and the whole reduction on a small trace of the engine recorded
on a TPU v5e by ``record_engine_fixture.py``."""

import os

import pytest

from chipbench import programtrace as PT
from chipbench import tracereduce as TR
from repro.runtime.tracing import Span

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_engine_1chip.xplane.pb")


def test_innermost_names_each_piece_by_the_deepest_span():
    spans = [("engine.step", 0, 100), ("engine.admit", 10, 40),
             ("engine.prefill", 10, 30), ("engine.first_token", 30, 40),
             ("engine.decode", 50, 80), ("engine.step", 120, 130),
             ("engine.step.decode", 115, 135)]
    assert PT.innermost(spans) == [
        ("engine.step", 0, 10), ("engine.prefill", 10, 30),
        ("engine.first_token", 30, 40), ("engine.step", 40, 50),
        ("engine.decode", 50, 80), ("engine.step", 80, 100),
        ("engine.step.decode", 115, 120), ("engine.step", 120, 130),
        ("engine.step.decode", 130, 135)]
    # a child running past its parent is cut at the parent's end
    assert PT.innermost([("a", 0, 10), ("b", 5, 15)]) == [("a", 0, 5),
                                                           ("b", 5, 10)]


def test_clock_shift_is_the_least_that_keeps_runs_after_their_enqueue():
    runs = [("1", 100, 110), ("2", 200, 240), ("3", 300, 301)]
    enqueued = {"1": 103, "2": 205, "3": 301}
    completed = {"1": 130, "2": 251, "3": 320}
    assert PT.clock_shift(runs, enqueued, completed) == (5, 11)
    assert PT.clock_shift(runs, {}, completed) == (0.0, 0.0)


def _made_up():
    dev = TR.DeviceOps("/device:TPU:0", [
        ("fusion.1", 5, 25), ("fusion.2", 45, 95), ("fusion.3", 125, 128)])
    host = [("bench.trace", 0, 200), ("engine.step.admit", 0, 101),
            ("engine.step", 0, 100), ("engine.admit", 0, 40),
            ("engine.prefill", 0, 30), ("engine.first_token", 30, 40),
            ("engine.upload", 40, 50), ("engine.decode", 50, 60),
            ("engine.sample", 60, 98), ("engine.bookkeep", 98, 100),
            ("engine.step", 120, 130), ("engine.decode", 120, 124),
            ("traffic.wait", 150, 190)]
    modules = [[("jit_engine_prefill", 5, 25), ("jit_engine_decode", 45, 95),
                ("jit_engine_decode", 125, 128),
                ("jit_engine_decode", 190, 210)]]
    return PT.ProgramTrace(TR.Trace([dev], host), modules, [(0.0, 0.0)])


def test_reduce_attributes_idle_to_the_innermost_span():
    pt = _made_up()
    red = PT.reduce(pt, (0, 200))
    ns = 1e-9
    assert red.idle_by_span == pytest.approx({
        "engine.prefill": 10 * ns, "engine.first_token": 10 * ns,
        "engine.upload": 5 * ns, "engine.sample": 3 * ns,
        "engine.bookkeep": 2 * ns,
        "engine.step.admit": 1 * ns, "engine.decode": 4 * ns,
        "engine.step": 3 * ns})
    assert red.idle_outside_s == pytest.approx(89 * ns)  # 101-120, 130-200
    assert red.attributed_s + red.idle_outside_s == pytest.approx(red.idle_s)
    # the same idle time as the harness's own reduction
    base = TR.reduce(pt.trace, (0, 200))
    assert red.idle_s == pytest.approx(base.idle_share * base.window_s)
    assert red.steps == 2 and red.step_idle_s == pytest.approx(37 * ns)
    # the run past the window's end is not counted
    assert red.modules["jit_engine_decode"] == (2, pytest.approx(53 * ns))
    assert PT.decode_device_ms(red) == pytest.approx(26.5e-6)
    assert PT.host_idle_ms(red) == pytest.approx(18.5e-6)


def test_readers_return_none_without_the_recorder():
    pt = _made_up()
    pt.trace.host_spans = [sp for sp in pt.trace.host_spans
                           if not sp[0].startswith("engine.")]
    pt.modules = [[("jit_wrapped", 5, 25)]]
    red = PT.reduce(pt, (0, 200))
    assert red.idle_by_span == {} and red.idle_outside_s == red.idle_s
    assert PT.decode_device_ms(red) is None and PT.host_idle_ms(red) is None
    for read in (PT.admit_span_ms, PT.batch_occupancy):
        assert read(None, 0.0, 1.0) is None
    assert PT.decode_device_ms(None) is None


def test_host_clock_readers_on_a_hand_made_export():
    s = 1_000_000_000
    export = {"dropped": 0, "spans": [
        Span(1, "engine.admit", 2 * s, 2 * s + 70_000_000, 0, 5),
        Span(3, "engine.admit", 3 * s, 3 * s + 74_000_000, 2, 6),
        Span(5, "engine.admit", 9 * s, 9 * s + 90_000_000, 4, 7)],
        "steps": [
            {"admitted": 1, "active": 3, "n_slots": 8, "t1_ns": 2 * s},
            {"admitted": 0, "active": 8, "n_slots": 8, "t1_ns": 3 * s},
            {"admitted": 0, "active": 6, "n_slots": 8, "t1_ns": 4 * s},
            {"admitted": 0, "active": 1, "n_slots": 8, "t1_ns": 9 * s}]}
    # window [2 s, 8 s): the third admission and the last step lie past it
    assert PT.admit_span_ms(export, 2.0, 8.0) == pytest.approx(72.0)
    assert PT.batch_occupancy(export, 2.0, 8.0) == pytest.approx(87.5)


def test_recorded_engine_trace():
    pt = PT.load(FIXTURE)
    assert len(pt.trace.devices) == 1 and pt.trace.devices[0].ops
    window = TR.window_of(pt.trace, "bench.trace")
    red = PT.reduce(pt, window)
    names = {n for n, _, _ in pt.trace.host_spans if n.startswith("engine.")}
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.first_token", "engine.upload", "engine.decode",
            "engine.sample", "engine.bookkeep"} <= names
    # three requests admitted, one decode run per step that had a batch
    assert red.modules["jit_engine_prefill"][0] == 3
    assert red.modules["jit_engine_admit"][0] == 3
    n_decode = sum(1 for n, s, e in pt.trace.host_spans
                   if n == "engine.decode" and s >= window[0]
                   and e <= window[1])
    assert red.modules["jit_engine_decode"][0] == n_decode > 0
    # the device's clock, moved onto the host's: each decode run starts
    # after its dispatch began and ends before its read-back ended
    (shift, largest), = pt.shift_ns
    assert 0 < shift <= largest
    spans = sorted((sp for sp in pt.trace.host_spans
                    if sp[0] in ("engine.decode", "engine.sample")),
                   key=lambda sp: sp[1])
    runs = [m for m in pt.modules[0] if m[0] == "jit_engine_decode"]
    assert len(runs) * 2 == len(spans)
    for (_, s, e), (_, d0, _), (_, _, s1) in zip(runs, spans[::2],
                                                  spans[1::2]):
        assert d0 <= s and e <= s1
    base = TR.reduce(TR.load(FIXTURE), window)
    assert red.idle_s == pytest.approx(base.idle_share * base.window_s,
                                       rel=0.01)
    assert red.attributed_s + red.idle_outside_s == pytest.approx(red.idle_s)
    assert red.idle_outside_s > 0             # the sleep in traffic.wait
    assert 0 < PT.host_idle_ms(red)
    step_ms = [(e - s) * 1e-6 for n, s, e in pt.trace.host_spans
               if n == "engine.step" and s >= window[0] and e <= window[1]]
    assert red.steps == len(step_ms)
    assert 0 < PT.decode_device_ms(red) < max(step_ms)
