"""The trace reduction: interval arithmetic on hand-made cases (collectives
among them), and the whole reduction on a small trace recorded on a TPU
v5e by ``record_fixture.py``."""

import os

import pytest

from chipbench import tracereduce as TR

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
ANNOTATIONS = ("bench.trace", "engine.step.admit", "engine.step.decode",
               "traffic.wait", "prefill.step")


def test_union_merges_overlaps_and_drops_empty():
    got = TR.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)])
    assert got == [(0, 3), (5, 9)]
    assert TR.total(got) == 7


def test_subtract_and_clip():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert TR.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert TR.clip([(0, 10), (12, 15)], 5, 13) == [(5, 10), (12, 13)]


def test_async_collective_spans_start_to_done():
    ops = [("fusion.1", 0, 10), ("all-gather-start.2", 10, 11),
           ("fusion.3", 11, 20), ("all-gather-done.2", 30, 31),
           ("all-reduce.4", 40, 45), ("copy", 45, 46)]
    coll, other = TR.split_ops(ops)
    assert sorted(coll) == [(10, 31), (40, 45)]
    assert other == [(0, 10), (11, 20), (45, 46)]
    assert TR.collective_kind("all-to-all.17") == ("all-to-all", "")
    assert TR.collective_kind("fusion.all-reduce") is None


def test_reduce_on_a_made_up_trace():
    dev = TR.DeviceOps("/device:TPU:0", [
        ("fusion.1", 0, 100), ("all-reduce.2", 100, 150),
        ("fusion.3", 120, 130), ("fusion.1", 300, 400)])
    host = [("engine.step.decode", 0, 160), ("traffic.wait", 160, 300)]
    red = TR.reduce(TR.Trace([dev], host), (0, 500))
    assert red.busy_s == pytest.approx(250e-9)
    assert red.idle_share == pytest.approx(0.5)
    # the all-reduce runs alone on [100, 120) and [130, 150)
    assert red.collective_exposed_s == pytest.approx(40e-9)
    assert dict(red.idle_by_host) == pytest.approx(
        {"engine.step.decode": 10e-9, "traffic.wait": 140e-9,
         "host.other": 100e-9})
    assert red.top_ops[0] == ("fusion.1", pytest.approx(200e-9))


def test_recorded_trace():
    chips = 1
    path = os.path.join(FIXTURES, f"trace_{chips}chip.xplane.pb")
    tr = TR.load(path, host_names=ANNOTATIONS)
    assert len(tr.devices) == chips
    assert all(d.ops for d in tr.devices)
    window = TR.window_of(tr, "bench.trace")
    red = TR.reduce(tr, window)
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    names = dict(red.idle_by_host)
    assert names.get("traffic.wait", 0) > 0
    assert names.get("engine.step.decode", 0) > 0
    assert red.collective_exposed_s == 0      # one chip: no collective
