"""Benchmark driver: one function per paper table/figure + framework tables.

Prints ``name,us_per_call,derived,spread`` CSV rows per the repo
convention (``spread`` = best-of-N max-min gap in the same microsecond
units, 0 for single-sample rows; see docs/perfmodel.md "Noise
convention"), plus human-readable tables to stderr-like sections.
Sources:

  fig4_router_area      — paper Fig. 4 (area model vs published numbers)
  fig6_multicast        — paper Fig. 6 (closed-form batch path of the NoC
                          perf model vs milestones)
  comm_plan_fig6        — planner policy comparison over the Fig. 6 grid,
                          with the closed-form vs scalar-DES pricing ratio
  ring_fused_matmul     — overlap objective (FUSED_RING pricing): serial
                          vs max(comm, compute)+ramp over the Fig. 6 grid
  step_overlap          — comm_overlap_fraction of the modeled dbrx-132b
                          train_4k step through the resolved rules; fails
                          below the 0.50 floor, and check_baseline fails
                          any exact decrease vs the committed fraction
  pod_allreduce_compressed — int8 vs raw f32 pod gradient all-reduce
                          (the priced compressed_psum transfer); fails if
                          int8 stops beating raw on modeled cycles
  noc_flit_microbench   — vectorized flit simulator vs the object-based
                          reference on one congested multicast workload
  noc_mesh_scale        — vectorized simulator drain throughput per mesh
                          size (4x3 ... 16x16), bursty waves with
                          fast-forwarded quiescent gaps; all NoC rows are
                          timed best-of-3 (minima, not noisy samples)
  commcheck_scan        — wall time of the full commcheck static gate
                          (best-of-3); fails outright if the tree carries
                          findings, so the row doubles as the lint invariant
  calib_fit             — wall time of one calib.fit_soc_params round trip
                          (best-of-3); fails outright if the fit stops
                          recovering the ground truth it synthesized from
  comm_mode_bytes       — MoE mem vs mcast collective bytes (C2/C4, from
                          compiled HLO of the production step)
  roofline_table        — per (arch x shape x mesh) roofline terms from the
                          dry-run artifacts in experiments/dryrun/

``--bench-noc`` runs the NoC/planner/serve/calibration battery, writes it
to a JSON file (default BENCH_noc.json) and, with ``--baseline``, fails
when a row's us_per_call regresses past ``CI_BENCH_TOL`` (default 5x —
wall-clock noise on shared CI boxes is large) — the scripts/ci.sh
regression gate.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import random
import time

import numpy as np

from repro.core.comm import CommMode
from repro.core.noc.router import base_router_area, router_area
from repro.core.noc.perfmodel import SoCPerfModel, PAPER_MILESTONES
from repro.core.noc.simulator import MeshNoC, Message
from repro.core.noc.reference_sim import ReferenceMeshNoC
from repro.core.planner import (CommPlanner, TransferSpec,
                                comm_overlap_fraction, mode_mix,
                                modeled_step_cycles)
from repro.configs.espsoc_trafficgen import (CONSUMER_SWEEP, SIZE_SWEEP,
                                             BITWIDTH_SWEEP, DEST_SWEEP,
                                             MESH_SCALE_SWEEP)
from repro.launch.cache import enable_compile_cache

_ROWS = []


def _row(name: str, us: float, derived: str = "", spread: float = 0.0):
    """One CSV row.  ``spread`` is the best-of-N max-min gap in the same
    microsecond units as ``us`` (0 for single-sample rows) — the run-to-run
    noise band that rides next to the minimum; see docs/perfmodel.md
    ("Noise convention")."""
    _ROWS.append((name, us, derived, spread))
    print(f"{name},{us:.3f},{derived},{spread:.3f}")


# ------------------------------------------------------------- Fig. 4 ----

def fig4_router_area():
    print("# Fig4: post-synthesis router area (um^2), 12nm model")
    print("# bitwidth,dests,area_um2,overhead_vs_baseline")
    t0 = time.perf_counter()
    for w in BITWIDTH_SWEEP:
        base = base_router_area(w)
        for d in DEST_SWEEP:
            a = router_area(w, d)
            print(f"# {w},{d},{a:.0f},{a / base - 1:.3f}")
    us = (time.perf_counter() - t0) * 1e6 / (len(BITWIDTH_SWEEP) *
                                             len(DEST_SWEEP))
    checks = [
        abs(base_router_area(64) - 3620) < 1,
        abs(base_router_area(128) - 6230) < 1,
        abs(base_router_area(256) - 11520) < 1,
        router_area(64, 4) / base_router_area(64) < 1.30,
        router_area(128, 8) / base_router_area(128) < 1.30,
        router_area(256, 16) / base_router_area(256) < 1.30,
    ]
    _row("fig4_router_area", us,
         f"paper_checks={sum(checks)}/{len(checks)}")


# ------------------------------------------------------------- Fig. 6 ----

def fig6_multicast() -> float:
    """Prints the Fig. 6 grid; returns the max relative milestone error
    (the --fig6-check gate consumes it)."""
    print("# Fig6: multicast vs shared-memory speedup "
          "(closed-form batch path, bit-exact vs the scalar DES)")
    print("# consumers," + ",".join(f"{s//1024}KB" for s in SIZE_SWEEP))
    model = SoCPerfModel()
    t0 = time.perf_counter()
    sweep = model.sweep(CONSUMER_SWEEP, SIZE_SWEEP)
    dt = time.perf_counter() - t0
    for n in CONSUMER_SWEEP:
        print(f"# {n}," + ",".join(f"{sweep[(n, s)]:.2f}" for s in SIZE_SWEEP))
    errs = []
    for (n, s), target in PAPER_MILESTONES.items():
        got = sweep.get((n, s))
        if got is None:
            # a falsy-zero `or`-fallback here used to silently re-run the
            # scalar DES; a missing milestone point is a sweep-grid bug
            raise SystemExit(
                f"# FAIL: milestone point ({n} consumers, {s} bytes) absent "
                f"from the Fig. 6 sweep grid")
        errs.append(abs(got - target) / target)
        print(f"# milestone ({n} consumers, {s//1024}KB): model {got:.2f} "
              f"vs paper {target:.2f} ({(got-target)/target:+.1%})")
    _row("fig6_multicast_speedup", dt * 1e6 / len(sweep),
         f"max_milestone_err={max(errs):.3f}")
    return max(errs)


def comm_plan_fig6() -> bool:
    """Planner policy comparison over the Fig. 6 grid: the cost-model-driven
    ``auto`` plan vs the two constant policies (always-MEM = the paper's
    shared-memory baseline; always-MCAST = always take the direct path).

    Returns True when the acceptance checks hold: the planner selects MCAST
    at all three paper milestones, its predicted speedup over always-MEM is
    within +-10% of the quoted 1.72x / 2.20x / 3.03x, and the auto plan is
    never slower than either constant policy at any grid point.
    """
    print("# CommPlanner policies over the Fig. 6 grid (cycles per point)")
    print("# consumers,bytes,mem,mcast,auto_mode,auto,auto_vs_mem")
    planner = CommPlanner()
    grid = [(n, s) for n in CONSUMER_SWEEP for s in SIZE_SWEEP]
    specs = [TransferSpec(f"xfer_{n}x{s}.L{i}", nbytes=s, fan_out=n, layer=i)
             for i, (n, s) in enumerate(grid)]
    t0 = time.perf_counter()
    decisions = planner.price(specs)       # one closed-form model sweep
    dt = time.perf_counter() - t0
    # per-layer mode mix: the planner's verdicts are per transfer (layer),
    # not one step-level mode — an empty mix means the pricing produced no
    # decisions at all, which is a planner bug, not a benchmark result
    mix = mode_mix(decisions)
    if sum(mix.values()) == 0:
        raise SystemExit("# FAIL: comm_plan_fig6 produced an empty per-layer "
                         "mode mix — the planner returned no decisions")
    # the same pricing through the scalar DES, for the speedup report
    model = planner.model
    t0 = time.perf_counter()
    for n, s in grid:
        model.shared_memory_cycles(n, s)
        model.multicast_cycles(n, s)
    dt_scalar = time.perf_counter() - t0
    tot = {"mem": 0.0, "mcast": 0.0, "auto": 0.0}
    never_slower = True
    for (n, s), d in zip(grid, decisions):
        mem, mcast = d.cycles["mem"], d.cycles["mcast"]
        auto = d.cycles["mem"] if d.mode is CommMode.MEM else d.cycles["mcast"]
        tot["mem"] += mem
        tot["mcast"] += mcast if np.isfinite(mcast) else mem
        tot["auto"] += auto
        never_slower &= auto <= mem + 1e-9 and (
            not np.isfinite(mcast) or auto <= mcast + 1e-9)
        print(f"# {n},{s},{mem:.0f},{mcast:.0f},{d.mode.name},{auto:.0f},"
              f"{mem / auto:.2f}x")
    milestones_ok = 0
    for (n, s), target in PAPER_MILESTONES.items():
        d = decisions[grid.index((n, s))]
        ok = (d.mode is CommMode.MCAST and
              abs(d.speedup_vs_mem - target) / target <= 0.10)
        milestones_ok += ok
        print(f"# milestone ({n} consumers, {s//1024}KB): mode={d.mode.name} "
              f"planner {d.speedup_vs_mem:.2f}x vs paper {target:.2f}x "
              f"-> {'OK' if ok else 'FAIL'}")
    passed = milestones_ok == len(PAPER_MILESTONES) and never_slower
    _row("comm_plan_fig6", dt * 1e6 / len(grid),
         f"mix=MEM:{mix['MEM']}/P2P:{mix['P2P']}/MCAST:{mix['MCAST']};"
         f"auto_vs_mem={tot['mem'] / tot['auto']:.2f}x;"
         f"auto_vs_mcast={tot['mcast'] / tot['auto']:.2f}x;"
         f"milestones_ok={milestones_ok}/{len(PAPER_MILESTONES)};"
         f"never_slower={never_slower};"
         f"vs_scalar_des={dt_scalar / max(dt, 1e-9):.1f}x")
    return passed


# ------------------------------------------------- flit simulator rows ----

def _scale_traffic(w, h, n_msgs, fan, n_flits, seed=2, waves=1, wave_gap=0):
    """Randomized multicast traffic; with ``waves > 1`` messages inject in
    bursty waves ``wave_gap`` cycles apart — the quiescent gaps between
    waves are what the vectorized stepper's fast-forward skips."""
    rng = random.Random(seed)
    nodes = [(x, y) for x in range(w) for y in range(h)]
    fan = min(fan, len(nodes))
    per_wave = max(1, n_msgs // waves)
    return [(rng.choice(nodes), tuple(rng.sample(nodes, fan)), n_flits,
             (i // per_wave) * wave_gap)
            for i in range(n_msgs)]


def _drain(noc_cls, w, h, msgs):
    noc = noc_cls(w, h)
    t0 = time.perf_counter()
    for src, dests, n, at in msgs:
        noc.inject(Message(src, dests, n, inject_cycle=at))
    cycles = noc.drain()
    dt = time.perf_counter() - t0
    return dt, cycles, noc


def _best_of(n, fn):
    """Best-of-N wall clock (compares minima, like
    ``socket_dispatch_overhead``): shared benchmark boxes jitter by tens
    of percent, and the CI_BENCH_TOL gate should see the machine's floor,
    not one noisy sample.  Returns ``(best_result, spread_seconds)`` where
    the spread is the max-min gap of the timed element ``r[0]`` across the
    N samples — the noise band the ``spread`` CSV column reports."""
    results = [fn() for _ in range(n)]
    times = [r[0] for r in results]
    return min(results, key=lambda r: r[0]), max(times) - min(times)


def noc_flit_microbench():
    """Vectorized stepper vs the object-based reference on one congested
    16x16 multicast workload (identical traffic; the property tests prove
    the two deliver identical flit sequences).  Best-of-3 on both sides."""
    w, h = 16, 16
    msgs = _scale_traffic(w, h, n_msgs=384, fan=16, n_flits=16)
    (dt_vec, cycles, noc), sp = _best_of(
        3, lambda: _drain(MeshNoC, w, h, msgs))
    (dt_ref, cycles_ref, _), _ = _best_of(
        3, lambda: _drain(ReferenceMeshNoC, w, h, msgs))
    assert cycles == cycles_ref, (cycles, cycles_ref)
    delivered = sum(len(v) for v in noc._dlog().values())
    _row("noc_flit_microbench", dt_vec * 1e6,
         f"mesh=16x16;msgs=384;fan=16;cycles={cycles};"
         f"flits_delivered={delivered};hops={noc.total_hops};"
         f"ref_us={dt_ref * 1e6:.0f};vs_reference={dt_ref / dt_vec:.1f}x",
         spread=sp * 1e6)


def noc_mesh_scale():
    """Drain throughput of the vectorized simulator across mesh sizes up
    to 16x16 (the pod-scale envelope the property tests validate),
    best-of-3.  Traffic arrives in four bursty waves with quiescent gaps
    between them — the fast-forward jumps each gap straight to the next
    injection cycle instead of stepping it (``ffwd`` in the derived
    column counts the skipped cycles)."""
    for (w, h) in MESH_SCALE_SWEEP:
        n_nodes = w * h
        msgs = _scale_traffic(w, h, n_msgs=6 * n_nodes,
                              fan=min(8, n_nodes), n_flits=8, seed=1,
                              waves=4, wave_gap=4096)
        (dt, cycles, noc), sp = _best_of(
            3, lambda: _drain(MeshNoC, w, h, msgs))
        delivered = sum(len(v) for v in noc._dlog().values())
        _row(f"noc_mesh_scale_{w}x{h}", dt * 1e6,
             f"msgs={len(msgs)};cycles={cycles};ffwd={noc.ffwd_cycles};"
             f"flits_delivered={delivered};hops={noc.total_hops};"
             f"khops_per_s={noc.total_hops / dt / 1e3:.0f}",
             spread=sp * 1e6)


# ----------------------------------------------- overlap objective row ----

def ring_fused_matmul():
    """Overlap-aware pricing of matmul-adjacent transfers (the FUSED_RING
    dispatch's cost-model side): the Fig. 6 grid re-priced with each
    transfer feeding a consumer matmul of moderate arithmetic intensity,
    compared serial (compute waits for comm) vs overlapped
    (``max(comm, compute) + ramp`` for fusible modes).  Fails loudly if
    the overlap objective ever prices WORSE than serial (the planner's
    property-tested invariant) or nothing fuses."""
    planner = CommPlanner()
    grid = [(n, s) for n in CONSUMER_SWEEP for s in SIZE_SWEEP]
    # ~64 FLOPs per transferred byte: a matmul consumer whose compute is
    # on the order of the transfer itself — the regime overlap targets
    specs = [TransferSpec(f"fused_{n}x{s}.L{i}", nbytes=s, fan_out=n,
                          layer=i, compute_flops=64.0 * s)
             for i, (n, s) in enumerate(grid)]
    t0 = time.perf_counter()
    decisions = planner.price(specs)
    dt = time.perf_counter() - t0
    serial = modeled_step_cycles(decisions, objective="serial")
    overlap = modeled_step_cycles(decisions)
    frac = comm_overlap_fraction(decisions)
    fused = sum(d.fused for d in decisions)
    if overlap > serial + 1e-9:
        raise SystemExit("# FAIL: overlap objective priced worse than "
                         f"serial ({overlap} > {serial})")
    if fused == 0:
        raise SystemExit("# FAIL: ring_fused_matmul fused no transfers — "
                         "the overlap objective is dead")
    mix = mode_mix(decisions)
    _row("ring_fused_matmul", dt * 1e6 / len(specs),
         f"fused={fused}/{len(specs)};"
         f"mix=MEM:{mix['MEM']}/P2P:{mix['P2P']}/MCAST:{mix['MCAST']};"
         f"overlap_vs_serial={serial / overlap:.2f}x;"
         f"comm_hidden={frac:.1%}")


# ------------------------------------------------ whole-step overlap ----

def step_overlap():
    """Comm-overlap fraction of the full dbrx-132b train_4k step on the
    16x16 mesh — the headline the fused MoE dispatch chain and the
    double-buffered FSDP weight stream buy.  The specs are the modeled
    step (``step_transfer_specs`` with the roofline compute pool
    attached), priced by the planner and gated through the RESOLVED
    sharding rules (``resolve_rules`` applied to the plan, exactly the
    dryrun's relower-once path).  Fails outright below the 0.50 floor;
    ``check_baseline`` additionally fails any regression of the fraction
    against the committed baseline (it is closed-form and deterministic,
    so the gate is exact)."""
    from repro.configs import SHAPES, get_config
    from repro.core.planner import step_transfer_specs
    from repro.core.sharding import resolve_rules
    from repro.runtime.train import TRAIN_RULES

    cfg = get_config("dbrx-132b")
    shape = SHAPES["train_4k"]
    mesh_axes = {"data": 16, "model": 16}
    specs = step_transfer_specs(cfg, shape, mesh_axes, with_compute=True)
    planner = CommPlanner()
    t0 = time.perf_counter()
    plan, decisions = planner.plan_with_decisions(specs)
    resolved, overlay = resolve_rules(plan, dict(TRAIN_RULES))
    frac = comm_overlap_fraction(decisions, resolved)
    dt = time.perf_counter() - t0
    serial = modeled_step_cycles(decisions, resolved, objective="serial")
    overlap = modeled_step_cycles(decisions, resolved)
    fused = sum(1 for d in decisions if d.fused or d.streamed)
    if frac < 0.50:
        raise SystemExit(f"# FAIL: step_overlap comm_overlap_fraction "
                         f"{frac:.4f} < 0.50 — the fused step regressed")
    if overlap > serial + 1e-9:
        raise SystemExit("# FAIL: step_overlap priced overlap worse than "
                         f"serial ({overlap} > {serial})")
    _row("step_overlap", dt * 1e6 / max(len(specs), 1),
         f"arch=dbrx-132b;shape=train_4k;mesh=16x16;"
         f"overlap_frac={frac:.4f};fused={fused}/{len(decisions)};"
         f"overlay={','.join(sorted(overlay)) or 'none'};"
         f"serial_vs_overlap={serial / overlap:.2f}x")


# ------------------------------------------ compressed pod all-reduce ----

def pod_allreduce_compressed():
    """Priced pod-axis gradient all-reduce: raw f32 vs the int8 transfer
    ``optim.compression.compressed_psum`` issues through its
    ``TransferDescriptor`` site (word_bytes=1 — one wire byte per
    gradient element, the ``grad_reduce_compressed`` spec the planner
    prices).  Both sides best-of-3 (minima); the row fails loudly if the
    compressed transfer ever stops beating raw on modeled cycles — the
    whole point of quantizing the inter-pod hop."""
    pods = 8
    raw = [TransferSpec(f"grad_raw_{s}.L{i}", nbytes=4 * s, fan_out=pods,
                        layer=i, reduce=True, word_bytes=4)
           for i, s in enumerate(SIZE_SWEEP)]
    comp = [TransferSpec(f"grad_int8_{s}.L{i}", nbytes=s, fan_out=pods,
                         layer=i, reduce=True, word_bytes=1)
            for i, s in enumerate(SIZE_SWEEP)]

    def _price(specs):
        t0 = time.perf_counter()
        decisions = CommPlanner().price(specs)
        return time.perf_counter() - t0, decisions

    (dt_raw, dec_raw), _ = _best_of(3, lambda: _price(raw))
    (dt_c, dec_c), sp = _best_of(3, lambda: _price(comp))
    if any(d.mode is not CommMode.MEM for d in dec_raw + dec_c):
        raise SystemExit("# FAIL: pod_allreduce_compressed — a reduction "
                         "priced off the memory tile (NoC cannot combine "
                         "in flight)")
    cyc_raw = modeled_step_cycles(dec_raw)
    cyc_c = modeled_step_cycles(dec_c)
    if cyc_c >= cyc_raw:
        raise SystemExit("# FAIL: pod_allreduce_compressed — int8 pod "
                         f"all-reduce stopped beating raw ({cyc_c:.0f} >= "
                         f"{cyc_raw:.0f} modeled cycles)")
    _row("pod_allreduce_compressed", dt_c * 1e6 / len(comp),
         f"pods={pods};bytes_raw={sum(s.nbytes for s in raw)};"
         f"bytes_int8={sum(s.nbytes for s in comp)};"
         f"cycles_raw={cyc_raw:.0f};cycles_int8={cyc_c:.0f};"
         f"cycles_saved={(cyc_raw - cyc_c) / cyc_raw:.1%};"
         f"raw_price_us={dt_raw * 1e6 / len(raw):.3f}",
         spread=sp * 1e6 / len(comp))


# -------------------------------------------- socket dispatch overhead ----

def socket_dispatch_overhead():
    """Per-issue cost of the descriptor-based socket path (plan lookup +
    control-beat build + ISA user-field encode — everything
    ``AcceleratorSocket.resolve`` does at trace time) vs the direct-call
    baseline (the bare plan-dict lookup a hardcoded collective site pays).
    Both sides best-of-3; the overhead is per *trace*, never per step."""
    from repro.core.comm import CommPlan, TransferDescriptor
    from repro.core.socket import AcceleratorSocket, StageRegistry

    reg = StageRegistry("stage")
    reg.register("prefill", 0)
    for i in (1, 2, 3):
        reg.register(f"decode{i}", i)
    plan = CommPlan({"kv_prefix": CommMode.MCAST,
                     "stage_activation": CommMode.P2P})
    sock = AcceleratorSocket(reg, plan)
    desc = TransferDescriptor("kv_prefix", source="prefill",
                              dests=("decode1", "decode2", "decode3"))
    n = 20000

    def best(fn, reps=3):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times), max(times) - min(times)

    def socket_side():
        for _ in range(n):
            sock.resolve(desc, 1 << 16, "write")

    def direct_side():
        for _ in range(n):
            plan.mode(desc.name)

    dt_sock, sp = best(socket_side)
    dt_direct, _ = best(direct_side)
    _row("socket_dispatch_overhead", dt_sock * 1e6 / n,
         f"direct_us={dt_direct * 1e6 / n:.3f};"
         f"vs_direct={dt_sock / max(dt_direct, 1e-12):.1f}x;"
         f"per_trace_not_per_step=True",
         spread=sp * 1e6 / n)


# ---------------------------------------------------------- serve load ----

def serve_load():
    """Continuous-batching serving throughput/latency under a
    deterministic Poisson arrival trace (``runtime.engine``), one dense
    and one MoE reduced config.  The row's ``us_per_call`` is
    microseconds per generated token (1e6 / tokens-per-second), best-of-3
    full trace runs on a fresh engine each time (trace + compile cost is
    amortized inside the run, exactly as a serving process would pay it);
    p50/p99 request latency ride the derived column.  Gated against
    BENCH_noc_baseline.json by CI_BENCH_TOL like every other row."""
    from repro.configs import get_reduced
    from repro.core import socket as socket_mod
    from repro.runtime.engine import ServeEngine, poisson_trace

    S, GEN, SLOTS, BS, NREQ = 16, 8, 3, 8, 6
    for arch in ("qwen3-4b", "dbrx-132b"):
        cfg = get_reduced(arch)

        def run():
            socket_mod.reset_issue_log()
            eng = ServeEngine(cfg, prompt_len=S, max_new_tokens=GEN,
                              n_slots=SLOTS, block_size=BS)
            trace = poisson_trace(NREQ, rate=0.8, prompt_len=S,
                                  vocab=cfg.vocab_size,
                                  max_new_tokens=GEN, seed=3)
            t0 = time.perf_counter()
            m = eng.run(trace)
            return time.perf_counter() - t0, m

        (dt, m), sp = _best_of(3, run)
        us = 1e6 / max(m.tokens_per_s, 1e-9)
        # spread in the row's own per-token units: the relative wall-clock
        # band applied to the reported us_per_call
        _row(f"serve_load_{arch}", us,
             f"tok_s={m.tokens_per_s:.1f};"
             f"p50_ms={m.p50_latency_s * 1e3:.1f};"
             f"p99_ms={m.p99_latency_s * 1e3:.1f};"
             f"requests={m.n_requests};steps={m.steps};"
             f"poisson_seed=3",
             spread=us * sp / max(dt, 1e-12))


# ------------------------------------------------------- commcheck scan ----

def commcheck_scan():
    """Wall time of the full commcheck static gate (the same scan
    scripts/ci.sh runs), best-of-3.  The row keeps the analyzer honest on
    two axes: it must stay fast enough to run on every commit (no jax
    import, one AST parse per file), and the tree it scans must stay
    clean — a finding here fails the bench like a regression."""
    from repro.analysis import DEFAULT_ALLOWLIST, analyze

    roots = [p for p in ("src/repro", "examples", "benchmarks", "scripts")
             if os.path.exists(p)]
    allow = DEFAULT_ALLOWLIST if os.path.exists(DEFAULT_ALLOWLIST) else None
    times, report = [], None
    for _ in range(3):
        t0 = time.perf_counter()
        report = analyze(roots, allowlist_path=allow)
        times.append(time.perf_counter() - t0)
    if not report.ok:
        for f in report.findings:
            print(f"# COMMCHECK FAIL: {f.render()}")
        raise SystemExit(1)
    _row("commcheck_scan", min(times) * 1e6,
         f"files={len(report.files)};findings=0;"
         f"suppressed={len(report.suppressed)};"
         f"allowlisted={len(report.allowlisted)}",
         spread=(max(times) - min(times)) * 1e6)


# ------------------------------------------------------- calibration fit ----

def calib_fit_bench():
    """Wall time of one full ``calib.fit_soc_params`` round trip (grid
    search over burst x link + the closed-form flops fit) on the standard
    flit-sim observation grid, best-of-3.  Like ``commcheck_scan`` the row
    doubles as an invariant: it fails outright if the fit stops recovering
    the ground-truth ``SoCParams`` it synthesized from — the calibration
    loop's end-to-end correctness, timed."""
    import dataclasses as _dc

    from repro.calib import fit as calib_fit, measure
    from repro.core.noc.perfmodel import SoCParams

    truth = SoCParams(link_latency=2, burst_bytes=8192,
                      flops_per_cycle=4096.0)
    obs = (measure.flit_sim_observations(truth) +
           measure.compute_observations(truth))
    base = _dc.replace(truth, link_latency=1, burst_bytes=4096,
                       flops_per_cycle=8192.0)

    def run():
        t0 = time.perf_counter()
        cp = calib_fit.fit_soc_params(obs, base=base)
        return time.perf_counter() - t0, cp

    run()   # warm the flit-sim cache: time the fit, not the simulations
    (dt, cp), sp = _best_of(3, run)
    ok = (cp.params.link_latency == truth.link_latency and
          cp.params.burst_bytes == truth.burst_bytes and
          abs(cp.params.flops_per_cycle - truth.flops_per_cycle)
          / truth.flops_per_cycle < 1e-6)
    if not ok:
        raise SystemExit("# FAIL: calib_fit stopped recovering the "
                         f"ground truth ({cp.params.link_latency}, "
                         f"{cp.params.burst_bytes}, "
                         f"{cp.params.flops_per_cycle:g})")
    _row("calib_fit", dt * 1e6,
         f"n_obs={cp.n_obs};residual={cp.residual:.5f};"
         f"recovered=link:{cp.params.link_latency}/"
         f"burst:{cp.params.burst_bytes}/"
         f"fpc:{cp.params.flops_per_cycle:g}",
         spread=sp * 1e6)


# ---------------------------------------------- comm modes (C2/C4, HLO) ----

def comm_mode_bytes():
    """Collective wire bytes of the dbrx MoE layer under the two modes —
    the production-framework analogue of Fig. 6 (multicast vs memory)."""
    import jax
    if len(jax.devices()) < 2:
        # measured from the persisted dry-run artifacts instead (the
        # matrix runs in a 512-device process)
        mem = _load_cell("dbrx-132b", "train_4k", "16x16", "mem")
        mc = _load_cell("dbrx-132b", "train_4k", "16x16", "mcast")
        if mem is None or mc is None:
            _row("comm_mode_bytes", 0.0, "needs dryrun artifacts (mem+mcast)")
            return
        b_mem = mem["roofline"]["wire_bytes_per_dev"]
        b_mc = mc["roofline"]["wire_bytes_per_dev"]
        _row("comm_mode_bytes", 0.0,
             f"mem_GB={b_mem/1e9:.2f};mcast_GB={b_mc/1e9:.2f};"
             f"saving={1 - b_mc / b_mem:.1%}")
        return
    # multi-device host: lower the reduced MoE step under both modes and
    # count collective wire bytes from the compiled HLO directly.  (The
    # dryrun import sets XLA_FLAGS, but jax is already initialized here, so
    # the device count cannot change.)
    try:
        from repro.configs import get_reduced
        from repro.configs.base import ShapeConfig
        from repro.launch.dryrun import build_comm_plan, lower_cell, make_flags
        from repro.launch.hlo_analysis import parse_collectives

        n = len(jax.devices())
        grid = (n // 2, 2) if n >= 4 else (1, n)
        mesh = jax.make_mesh(grid, ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_reduced("dbrx-132b")
        shape = ShapeConfig("bench", 128, 4 * grid[0], "train")
        t0 = time.perf_counter()
        wire = {}
        for policy in ("mem", "mcast"):
            plan, _ = build_comm_plan(policy, cfg, shape, mesh)
            flags = make_flags(cfg, shape, moe_mode=policy)
            lowered, _ = lower_cell(cfg, shape, mesh, flags, comm_plan=plan)
            colls = parse_collectives(lowered.compile().as_text())
            wire[policy] = sum(c.wire_bytes for c in colls.values())
        dt = time.perf_counter() - t0
        saving = (1 - wire["mcast"] / wire["mem"]) if wire["mem"] else 0.0
        _row("comm_mode_bytes", dt * 1e6 / 2,
             f"devices={n};mem_MB={wire['mem']/1e6:.2f};"
             f"mcast_MB={wire['mcast']/1e6:.2f};saving={saving:.1%}")
    except Exception as e:   # noqa: BLE001 - report, don't hide, the skip
        _row("comm_mode_bytes", 0.0,
             f"skipped={type(e).__name__}: {str(e)[:80]}")


def _load_cell(arch, shape, mesh, mode=None, tag=""):
    suffix = (f"_{mode}" if mode else "") + (f"_{tag}" if tag else "")
    path = f"experiments/dryrun/{arch}_{shape}_{mesh}{suffix}.json"
    if not os.path.exists(path):
        return None
    return json.load(open(path))


# -------------------------------------------------------- roofline table ----

def roofline_table():
    print("# Roofline per (arch x shape x mesh) from dry-run artifacts")
    print("# arch,shape,mesh,compute_ms,memory_ms,collective_ms,dominant,"
          "useful_flops_ratio,roofline_fraction,peak_GiB,fits16GB")
    files = sorted(glob.glob("experiments/dryrun/*.json"))
    n = 0
    worst = (1.0, None)
    for f in files:
        d = json.load(open(f))
        if d.get("skipped") or d.get("moe_mode") == "mcast":
            continue
        if "_hc" in os.path.basename(f):
            continue
        r, m = d["roofline"], d["memory"]
        print(f"# {d['arch']},{d['shape']},{d['mesh']},"
              f"{r['compute_s']*1e3:.1f},{r['memory_s']*1e3:.1f},"
              f"{r['collective_s']*1e3:.1f},{r['dominant']},"
              f"{r['useful_flops_ratio']:.2f},{r['roofline_fraction']:.4f},"
              f"{m['peak_bytes_est_per_dev']/2**30:.1f},"
              f"{m['fits_16gb']}")
        n += 1
        if r["roofline_fraction"] < worst[0]:
            worst = (r["roofline_fraction"], f"{d['arch']}x{d['shape']}")
    _row("roofline_table", 0.0, f"cells={n};worst={worst[1]}")


# ------------------------------------------------------------ NoC gate ----

def write_bench_json(path: str) -> None:
    rows = {name: {"us_per_call": us, "derived": derived, "spread": spread}
            for name, us, derived, spread in _ROWS}
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, sort_keys=True)
    print(f"# wrote {path} ({len(rows)} rows)")


def _derived_overlap_frac(derived: str):
    """Parse ``overlap_frac=0.xxxx`` out of a derived column (None when
    the row carries no fraction)."""
    for part in derived.split(";"):
        if part.startswith("overlap_frac="):
            return float(part.split("=", 1)[1])
    return None


def check_baseline(baseline_path: str) -> bool:
    """Compare the collected rows against a committed baseline: fail when a
    row's us_per_call regressed past CI_BENCH_TOL (wall-clock multiplier,
    default 5 — shared CI boxes are noisy) or a baseline row went missing.
    Rows carrying ``overlap_frac=`` in their derived column (step_overlap)
    are additionally gated EXACTLY: the fraction is closed-form model
    output, not wall clock, so any decrease is a planner regression."""
    tol = float(os.environ.get("CI_BENCH_TOL", "5"))
    with open(baseline_path) as f:
        base = json.load(f)
    rows = {name: (us, derived) for name, us, derived, _ in _ROWS}
    ok = True
    for name, entry in base.items():
        if name not in rows:
            print(f"# BENCH FAIL: row {name} missing from this run")
            ok = False
            continue
        b = entry["us_per_call"]
        got, derived = rows[name]
        if b > 0 and got > b * tol:
            print(f"# BENCH FAIL: {name} {got:.0f}us vs baseline {b:.0f}us "
                  f"(> {tol:.0f}x)")
            ok = False
        else:
            print(f"# bench ok: {name} {got:.0f}us (baseline {b:.0f}us)")
        base_frac = _derived_overlap_frac(entry.get("derived", ""))
        if base_frac is not None:
            frac = _derived_overlap_frac(derived)
            if frac is None or frac + 1e-9 < base_frac:
                print(f"# BENCH FAIL: {name} overlap_frac "
                      f"{'missing' if frac is None else f'{frac:.4f}'} vs "
                      f"baseline {base_frac:.4f} — overlap regressed")
                ok = False
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fig6-check", action="store_true",
                    help="run only the Fig. 6 model + planner milestone "
                         "checks and exit nonzero on failure (CI gate)")
    ap.add_argument("--bench-noc", action="store_true",
                    help="run the NoC benchmark rows, write them to --out "
                         "and compare against --baseline (CI gate)")
    ap.add_argument("--out", default="BENCH_noc.json")
    ap.add_argument("--baseline", default="")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived,spread")
    if args.fig6_check:
        max_err = fig6_multicast()
        ok = comm_plan_fig6()
        if max_err > 0.10:
            print(f"# FAIL: Fig. 6 milestone error {max_err:.1%} > 10%")
            raise SystemExit(1)
        if not ok:
            print("# FAIL: planner policy checks failed")
            raise SystemExit(1)
        print("# fig6-check passed")
        return
    if args.bench_noc:
        fig6_multicast()
        comm_plan_fig6()
        ring_fused_matmul()
        step_overlap()
        pod_allreduce_compressed()
        noc_flit_microbench()
        noc_mesh_scale()
        socket_dispatch_overhead()
        commcheck_scan()
        serve_load()
        calib_fit_bench()
        write_bench_json(args.out)
        if args.baseline:
            if not check_baseline(args.baseline):
                raise SystemExit(1)
            print("# bench-noc baseline check passed")
        return
    fig4_router_area()
    fig6_multicast()
    comm_plan_fig6()
    ring_fused_matmul()
    step_overlap()
    pod_allreduce_compressed()
    noc_flit_microbench()
    noc_mesh_scale()
    socket_dispatch_overhead()
    commcheck_scan()
    serve_load()
    calib_fit_bench()
    comm_mode_bytes()
    roofline_table()
    write_bench_json(args.out)


if __name__ == "__main__":
    main()
