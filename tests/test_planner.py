"""Cost-model-driven communication-mode planner (paper C4, automated).

Unit tests pin the planner to the paper's Fig. 6 preferences and the
header-flit capacity constraint; the subprocess test proves the plan flows
end-to-end through sharding/runtime/dryrun and actually switches the
collective that XLA emits.
"""

import numpy as np
import pytest

from repro.core.comm import (CommMode, mode_from_read_field,
                             mode_from_write_field)
from repro.core.noc.header import ESP_MAX_DESTS, max_multicast_dests
from repro.core.noc.perfmodel import PAPER_MILESTONES, SoCPerfModel
from repro.core.planner import CommPlanner, TransferSpec, step_transfer_specs


# ------------------------------------------------------- mode selection ----

def test_milestones_select_mcast_within_10pct():
    """Acceptance: at the three paper milestones the planner picks MCAST and
    its predicted speedup over always-MEM is within +-10% of the quoted
    1.72x / 2.20x / 3.03x."""
    planner = CommPlanner()
    specs = [TransferSpec(f"m{n}_{s}", nbytes=s, fan_out=n)
             for (n, s) in PAPER_MILESTONES]
    plan, decisions = planner.plan_with_decisions(specs)
    for d, ((n, s), target) in zip(decisions, PAPER_MILESTONES.items()):
        assert d.mode is CommMode.MCAST, (n, s, d.reason)
        assert plan.mode(d.spec.name) is CommMode.MCAST
        assert d.speedup_vs_mem == pytest.approx(target, rel=0.10), (n, s)


def test_fanout_crossover_mcast_to_mem():
    """Mode selection flips exactly at the multicast capacity: every
    feasible fan-out takes the direct path (the model predicts it faster at
    every Fig. 6 point), one past capacity degrades to MEM."""
    planner = CommPlanner()
    cap = planner.capacity
    assert cap == min(max_multicast_dests(SoCPerfModel().p.bitwidth),
                      ESP_MAX_DESTS)
    specs = [TransferSpec(f"f{n}", nbytes=65536, fan_out=n)
             for n in range(1, cap + 3)]
    decisions = planner.price(specs)
    for d in decisions:
        if d.spec.fan_out <= cap:
            assert d.mode is CommMode.MCAST, d
        else:
            assert d.mode is CommMode.MEM, d
            assert "capacity" in d.reason


def test_speedup_grows_with_size_at_max_fanout():
    """The Fig. 6 trend the milestones quote: at 16 consumers the multicast
    advantage grows with transfer size (1.72x @ 4KB ... 3.03x @ 1MB)."""
    planner = CommPlanner()
    sizes = (4096, 65536, 1048576)
    decisions = planner.price(
        [TransferSpec(f"s{s}", nbytes=s, fan_out=16) for s in sizes])
    speedups = [d.speedup_vs_mem for d in decisions]
    assert speedups == sorted(speedups)
    assert all(d.mode is CommMode.MCAST for d in decisions)


def test_narrower_noc_lowers_capacity():
    """A 64-bit NoC's header flit only fits 5 destinations (paper Fig. 4
    anchor): fan-out 6 must fall back to MEM there."""
    planner = CommPlanner(max_dests=max_multicast_dests(64))
    assert planner.capacity == 5
    d5, d6 = planner.price([TransferSpec("a", nbytes=65536, fan_out=5),
                            TransferSpec("b", nbytes=65536, fan_out=6)])
    assert d5.mode is CommMode.MCAST
    assert d6.mode is CommMode.MEM


def test_pull_unicast_is_p2p_push_is_mcast():
    planner = CommPlanner()
    pull, push = planner.price([
        TransferSpec("stage_activation", nbytes=65536, fan_out=1, pull=True),
        TransferSpec("trafficgen", nbytes=65536, fan_out=1)])
    assert pull.mode is CommMode.P2P       # read channel: consumer pulls
    assert push.mode is CommMode.MCAST     # write channel: 1-dest multicast
    # both ride the same direct path in the model
    assert pull.cycles["mcast"] == push.cycles["mcast"]


def test_zero_fanout_is_mem():
    (d,) = CommPlanner().price([TransferSpec("store", nbytes=4096, fan_out=0)])
    assert d.mode is CommMode.MEM


# ------------------------------------------------- user-field round-trip ----

def test_requests_user_field_roundtrip():
    """Planner-emitted CommRequests encode the paper's user fields, and the
    fields decode back to the planned mode."""
    planner = CommPlanner()
    specs = [
        TransferSpec("mcast4", nbytes=4096, fan_out=4),
        TransferSpec("pull1", nbytes=4096, fan_out=1, pull=True, source=3),
        TransferSpec("overflow", nbytes=4096, fan_out=100),
    ]
    reqs = planner.requests(specs)

    mc, p2p, mem = reqs
    assert mc.mode is CommMode.MCAST and mc.dests == (1, 2, 3, 4)
    assert mc.user_field_write() == 4
    assert mode_from_write_field(mc.user_field_write()) is CommMode.MCAST

    assert p2p.mode is CommMode.P2P and p2p.source == 3
    assert p2p.user_field_read() == 3
    assert mode_from_read_field(p2p.user_field_read()) is CommMode.P2P
    # write channel: a single destination encodes user=1 — the paper's
    # unicast degeneracy (1-dest multicast == P2P write)
    assert p2p.user_field_write() == 1
    assert mode_from_write_field(p2p.user_field_write()) is CommMode.P2P

    assert mem.mode is CommMode.MEM and mem.dests == ()
    assert mem.user_field_read() == 0 and mem.user_field_write() == 0
    assert mode_from_read_field(0) is CommMode.MEM
    assert mode_from_write_field(0) is CommMode.MEM

    # request length mirrors the control-channel beat: words * word size
    assert mc.nbytes == 4096


def test_write_field_degeneracy_documented():
    """MCAST with one destination and unicast P2P are the same wire
    transaction: both encode write user field 1."""
    planner = CommPlanner()
    (req,) = planner.requests([TransferSpec("uni", nbytes=4096, fan_out=1)])
    assert req.mode is CommMode.MCAST and len(req.dests) == 1
    assert req.user_field_write() == 1
    assert mode_from_write_field(req.user_field_write()) is CommMode.P2P


# ------------------------------------------------------ batched model API ----

def test_batch_cycles_matches_scalar_des():
    """The vectorized sweep is exact against the scalar discrete-event model
    (it exists to make planning cheap, not approximate)."""
    model = SoCPerfModel()
    pts = [(n, s) for n in (1, 2, 5, 16) for s in (4096, 65536, 1048576)]
    ns = np.array([p[0] for p in pts])
    ds = np.array([p[1] for p in pts])
    batch = model.batch_cycles(ns, ds)
    for i, (n, s) in enumerate(pts):
        assert batch["mem"][i] == pytest.approx(
            model.shared_memory_cycles(n, s), abs=1e-6), (n, s)
        assert batch["mcast"][i] == pytest.approx(
            model.multicast_cycles(n, s), abs=1e-6), (n, s)
    # p2p column is the unicast path wherever fan-out is 1
    one = ns == 1
    assert np.allclose(batch["p2p"][one], batch["mcast"][one])
    assert np.all(np.isnan(batch["p2p"][~one]))


def test_batch_cycles_capacity_nan_and_extrapolation():
    model = SoCPerfModel()
    over = model.batch_cycles(np.array([model.max_dests + 1]),
                              np.array([4096]))
    assert np.isnan(over["mcast"][0]) and np.isfinite(over["mem"][0])
    # beyond the burst cap: finite, monotone in size
    big = model.batch_cycles(np.array([4, 4]),
                             np.array([32 << 20, 64 << 20]))
    assert np.all(np.isfinite(big["mcast"]))
    assert big["mcast"][1] > big["mcast"][0]
    assert big["mem"][1] > big["mem"][0]


# ---------------------------------------------------------- step planning ----

def test_step_specs_weight_broadcast_degrades_multi_pod():
    """The paper's constraint at system scale: 16 data-parallel replicas fit
    the destination-set limit (MCAST weight broadcast); the 32-replica
    multi-pod mesh exceeds it and the planner degrades weights to MEM."""
    from repro.configs import get_config, SHAPES
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    planner = CommPlanner()

    single = planner.plan(step_transfer_specs(cfg, shape,
                                              {"data": 16, "model": 16}))
    multi = planner.plan(step_transfer_specs(
        cfg, shape, {"pod": 2, "data": 16, "model": 16}))
    assert single.mode("weights") is CommMode.MCAST
    assert multi.mode("weights") is CommMode.MEM
    # MoE dispatch (top-4) and the stage hand-off stay on the direct paths
    for plan in (single, multi):
        assert plan.mode("moe_dispatch") is CommMode.MCAST
        assert plan.mode("stage_activation") is CommMode.P2P


def test_step_specs_price_compressed_pod_gradients():
    """The pod-axis int8 gradient all-reduce is a real priced spec: one
    byte per element (4x fewer than f32), reduce-pinned, emitted only when
    the mesh has a pod axis."""
    from repro.configs import get_config, SHAPES
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]

    flat = {s.name: s for s in
            step_transfer_specs(cfg, shape, {"data": 16, "model": 16})}
    assert "grad_reduce_compressed" not in flat   # no pod axis: inactive

    pod = {s.name: s for s in step_transfer_specs(
        cfg, shape, {"pod": 4, "data": 16, "model": 16})}
    spec = pod["grad_reduce_compressed"]
    assert spec.reduce and spec.word_bytes == 1 and spec.fan_out == 4
    assert spec.nbytes == cfg.param_count() // 16   # int8: 1 B / element
    plan, dec = CommPlanner().plan_with_decisions(list(pod.values()))
    assert plan.mode("grad_reduce_compressed") is CommMode.MEM  # pinned
    d = {x.spec.name: x for x in dec}["grad_reduce_compressed"]
    assert "reduction" in d.reason or "combine" in d.reason


# ----------------------------------------------- HLO-derived transfers ----

_FAKE_HLO = """
ENTRY %main (p: f32[16,64]) -> f32[16,64] {
  %p = f32[16,64]{1,0} parameter(0)
  %ar = f32[16,64]{1,0} all-reduce(%p), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[256,64]{1,0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = f32[64,64]{1,0} all-to-all(%ar), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  ROOT %cp = f32[16,64]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""


def test_transfer_specs_from_hlo_archetypes():
    """Fan-out and bytes come from the lowered collective ops themselves:
    all-to-all -> per-peer unicast chunks, all-gather -> shard broadcast,
    all-reduce -> MEM-pinned reduction, collective-permute -> pull P2P."""
    from repro.launch.hlo_analysis import transfer_specs_from_hlo
    by_name = {s.name: s for s in transfer_specs_from_hlo(_FAKE_HLO)}

    a2a = by_name["moe_dispatch"]
    assert a2a.fan_out == 1 and not a2a.reduce
    assert a2a.nbytes == 64 * 64 * 4 // 8          # result bytes / group

    ag = by_name["weights"]
    assert ag.fan_out == 3                          # group 4 -> 3 peers
    assert ag.nbytes == 256 * 64 * 2 // 4           # per-shard bytes

    ar = by_name["grad_reduce"]
    assert ar.reduce and ar.fan_out == 15           # group 16
    assert ar.nbytes == 16 * 64 * 4

    cp = by_name["stage_activation"]
    assert cp.pull and cp.fan_out == 1
    assert cp.nbytes == 16 * 64 * 4


def test_transfer_specs_async_start_result_bytes():
    """Async -start collectives are tuple-typed (operand, result): pricing
    must use the result buffer, not the tuple sum ((g+1)/g over-count)."""
    from repro.launch.hlo_analysis import transfer_specs_from_hlo
    hlo = """
ENTRY %main (p: f32[16,64]) -> f32[64,64] {
  %p = f32[16,64]{1,0} parameter(0)
  %ags = (f32[16,64]{1,0}, f32[64,64]{1,0}) all-gather-start(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %agd = f32[64,64]{1,0} all-gather-done(%ags)
}
"""
    (ag,) = [s for s in transfer_specs_from_hlo(hlo) if s.name == "weights"]
    assert ag.nbytes == 64 * 64 * 4 // 4        # result bytes / group
    assert ag.fan_out == 3


def test_transfer_specs_fallback_merge():
    """Config estimates survive only for transfers the HLO does not
    exhibit; HLO-derived specs win on collisions and keep fallback order."""
    from repro.launch.hlo_analysis import transfer_specs_from_hlo
    fallback = [TransferSpec("weights", nbytes=999, fan_out=9),
                TransferSpec("custom_stream", nbytes=123, fan_out=2)]
    specs = transfer_specs_from_hlo(_FAKE_HLO, fallback=fallback)
    names = [s.name for s in specs]
    assert names[:2] == ["weights", "custom_stream"]
    by_name = {s.name: s for s in specs}
    assert by_name["weights"].nbytes != 999         # HLO-derived won
    assert by_name["custom_stream"].nbytes == 123   # config-only survives


def test_reduction_specs_pinned_to_mem():
    """The NoC forks multicast flits but cannot combine in flight: reduce
    transfers never take the direct path, whatever the model predicts."""
    (d,) = CommPlanner().price(
        [TransferSpec("grad_reduce", nbytes=65536, fan_out=4, reduce=True)])
    assert d.mode is CommMode.MEM
    assert "reduction" in d.reason


def test_resolve_policy_plan_cache():
    """--comm-plan=auto prices once per launch: identical (cfg, shape,
    mesh, policy) resolutions hit the cache, HLO-keyed ones included.
    Starts from a clean cache without clearing it itself — the autouse
    ``_reset_planner_state`` fixture guarantees no leakage across tests."""
    from repro.configs import get_config, SHAPES
    from repro.core.planner import plan_cache_stats, resolve_policy
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    axes = {"data": 16, "model": 16}
    assert plan_cache_stats() == {"hits": 0, "misses": 0, "size": 0}
    p1, d1 = resolve_policy("auto", cfg, shape, axes)
    p2, d2 = resolve_policy("auto", cfg, shape, axes)
    assert plan_cache_stats() == {"hits": 1, "misses": 1, "size": 1}
    assert dict(p1.modes) == dict(p2.modes) and d1 is d2
    h1, _ = resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO)
    h2, _ = resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO)
    stats = plan_cache_stats()
    assert stats["hits"] == 2 and stats["misses"] == 2
    assert h1.mode("grad_reduce") is CommMode.MEM


# a second module with DIFFERENT collectives (extra group member changes the
# all-gather bytes/fan-out): same policy + overlay must still miss the cache
_FAKE_HLO2 = _FAKE_HLO.replace("{{0,1,2,3}}", "{{0,1,2,3,4}}")


def test_plan_cache_overlay_and_collective_keying():
    """The cache key is (policy, profile, rule overlay, specs): same HLO +
    same overlay hits; a changed overlay or changed collectives misses."""
    from repro.configs import get_config, SHAPES
    from repro.core.planner import plan_cache_stats, resolve_policy
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    axes = {"data": 16, "model": 16}
    assert plan_cache_stats()["size"] == 0   # autouse fixture reset held

    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO)
    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO)
    assert plan_cache_stats() == {"hits": 1, "misses": 1, "size": 1}

    # same HLO, rule overlay applied -> distinct entry; repeat -> hit
    ov = {"w_fsdp": None}
    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO,
                   rules_overlay=ov)
    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO,
                   rules_overlay=dict(ov))
    assert plan_cache_stats() == {"hits": 2, "misses": 2, "size": 2}

    # changed overlay -> miss
    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO,
                   rules_overlay={"w_fsdp": "data"})
    assert plan_cache_stats()["misses"] == 3

    # changed collectives (different module) -> miss, same overlay or not
    resolve_policy("auto", cfg, shape, axes, hlo_text=_FAKE_HLO2,
                   rules_overlay=ov)
    stats = plan_cache_stats()
    assert stats["misses"] == 4 and stats["hits"] == 2 and stats["size"] == 4


def test_pod_profile_planner():
    """A pod-scale model prices through the same planner: the ESP cap still
    binds capacity and direct paths still win at feasible fan-outs."""
    from repro.core.noc.perfmodel import SoCParams
    planner = CommPlanner(SoCPerfModel(SoCParams.pod(16, 16)))
    assert planner.capacity == 16
    d8, d17 = planner.price([TransferSpec("a", nbytes=262144, fan_out=8),
                             TransferSpec("b", nbytes=262144, fan_out=17)])
    assert d8.mode is CommMode.MCAST and d8.speedup_vs_mem > 1.0
    assert d17.mode is CommMode.MEM and "capacity" in d17.reason


# ---------------------------------------------- per-layer + rule feedback ----

# a 4-iteration scan-over-layers body with an all-gather (weights) and an
# all-to-all (moe dispatch); one unscanned collective-permute in the entry
_FAKE_SCANNED_HLO = """
%cond.1 (c: (s32[], f32[16,64])) -> pred[] {
  %c = (s32[], f32[16,64]) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body.1 (b: (s32[], f32[16,64])) -> (s32[], f32[16,64]) {
  %b = (s32[], f32[16,64]) parameter(0)
  %i2 = s32[] get-tuple-element(%b), index=0
  %x = f32[16,64]{1,0} get-tuple-element(%b), index=1
  %ag = f32[64,64]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = f32[16,64]{1,0} all-to-all(%x), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %one = s32[] constant(1)
  %i3 = s32[] add(%i2, %one)
  ROOT %t = (s32[], f32[16,64]) tuple(%i3, %x)
}

ENTRY %main (p: f32[16,64]) -> f32[16,64] {
  %p = f32[16,64]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[16,64]) tuple(%zero, %p)
  %w = (s32[], f32[16,64]) while(%init), condition=%cond.1, body=%body.1
  %cp = f32[16,64]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
  ROOT %out = f32[16,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_per_layer_specs_from_scanned_hlo():
    """A collective inside the scan-over-layers while body (trip count 4)
    becomes four per-layer specs with stable ``.L<i>`` names; the unscanned
    entry-computation op keeps its bare archetype name."""
    from repro.launch.hlo_analysis import transfer_specs_from_hlo
    specs = transfer_specs_from_hlo(_FAKE_SCANNED_HLO)
    names = [s.name for s in specs]
    assert names == (["moe_dispatch.L%d" % i for i in range(4)] +
                     ["stage_activation"] +
                     ["weights.L%d" % i for i in range(4)])
    for s in specs:
        if s.name.startswith("weights"):
            assert s.fan_out == 3 and s.nbytes == 64 * 64 * 4 // 4
            assert s.layer == int(s.name.rsplit(".L", 1)[1])
    assert {s.layer for s in specs if s.name == "stage_activation"} == {None}


def test_per_layer_plan_publishes_base_aggregate():
    """Runtime collective sites query the logical archetype name; a layered
    plan publishes the dominant layer's mode under the base name."""
    from repro.launch.hlo_analysis import transfer_specs_from_hlo
    plan, decisions = CommPlanner().plan_with_decisions(
        transfer_specs_from_hlo(_FAKE_SCANNED_HLO))
    assert plan.mode("weights.L2") is CommMode.MCAST
    assert plan.mode("weights") is CommMode.MCAST
    assert plan.mode("moe_dispatch") is CommMode.MCAST
    assert plan.mode("stage_activation") is CommMode.P2P
    from repro.core.planner import mode_mix
    mix = mode_mix(decisions)
    assert mix["MCAST"] == 8 and mix["P2P"] == 1 and mix["MEM"] == 0


def test_resolve_rules_w_fsdp_overlay():
    """The feedback pass: weights planning to MCAST turns w_fsdp off
    (weights replicated + broadcast on the direct path); a MEM verdict —
    e.g. the 32-replica multi-pod broadcast past the destination cap —
    keeps FSDP.  The modeled step cost never gets worse and strictly
    improves when the overlay applies."""
    from repro.configs import get_config, SHAPES
    from repro.core.planner import modeled_step_cycles
    from repro.core.sharding import resolve_rules
    from repro.runtime.train import TRAIN_RULES
    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    planner = CommPlanner()

    plan_s, dec_s = planner.plan_with_decisions(
        step_transfer_specs(cfg, shape, {"data": 16, "model": 16}))
    rules_s, overlay_s = resolve_rules(plan_s, TRAIN_RULES)
    assert overlay_s == {"w_fsdp": None}
    assert rules_s["w_fsdp"] is None
    assert modeled_step_cycles(dec_s, rules_s) < \
        modeled_step_cycles(dec_s, TRAIN_RULES)

    plan_m, dec_m = planner.plan_with_decisions(step_transfer_specs(
        cfg, shape, {"pod": 2, "data": 16, "model": 16}))
    rules_m, overlay_m = resolve_rules(plan_m, TRAIN_RULES)
    assert overlay_m == {}
    assert rules_m["w_fsdp"] == TRAIN_RULES["w_fsdp"]
    assert modeled_step_cycles(dec_m, rules_m) == \
        modeled_step_cycles(dec_m, TRAIN_RULES)


# ------------------------------------------------------------ end-to-end ----

_E2E_CODE = r"""
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.core.comm import CommMode
from repro.launch.dryrun import build_comm_plan, lower_cell, make_flags

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_reduced("dbrx-132b")
shape = ShapeConfig("t", 128, 16, "train")
flags = make_flags(cfg, shape)

plan, decisions = build_comm_plan("auto", cfg, shape, mesh)
assert plan.mode("moe_dispatch") is CommMode.MCAST, plan.modes
assert plan.mode("stage_activation") is CommMode.P2P, plan.modes
assert decisions and all(d.speedup_vs_mem >= 1.0 for d in decisions)
lowered, _ = lower_cell(cfg, shape, mesh, flags, comm_plan=plan)
hlo_auto = lowered.compile().as_text()

mem_plan, _ = build_comm_plan("mem", cfg, shape, mesh)
lowered_mem, _ = lower_cell(cfg, shape, mesh, flags, comm_plan=mem_plan)
hlo_mem = lowered_mem.compile().as_text()

# the plan switched the collective XLA emits for MoE dispatch: the mcast
# path is all_to_all-based, the mem baseline is a psum combine
assert "all-to-all" in hlo_auto, "auto plan should lower to all-to-all dispatch"
assert "all-to-all" not in hlo_mem, "mem plan must not use all-to-all"
print("PLANNER_E2E_OK", flush=True)
"""


def test_dryrun_auto_plan_switches_collectives(subproc):
    """--comm-plan=auto reaches the lowered HLO: the planner's MCAST choice
    turns the MoE dispatch into the all_to_all path, the forced-MEM plan
    keeps the shared-memory combine."""
    out = subproc(_E2E_CODE, n_devices=16)
    assert "PLANNER_E2E_OK" in out
