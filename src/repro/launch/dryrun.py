import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**specs).compile()`` must succeed on the
single-pod (16, 16) mesh and the 2-pod (2, 16, 16) mesh for every assigned
architecture and shape.  ``memory_analysis()`` proves the per-device working
set fits; ``cost_analysis()`` + HLO collective parsing feed the roofline
(EXPERIMENTS.md §Roofline).

The XLA_FLAGS line above must execute before any other jax-touching import:
jax locks the device count on first initialization.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import dataclasses
import functools
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_NAMES, SHAPES, get_config, shape_applicable)
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.comm import CommMode
from repro.core import socket as socket_mod
from repro.core.planner import (comm_overlap_fraction, mode_mix,
                                modeled_step_cycles, refine_plan_from_hlo,
                                resolve_policy)
from repro.launch.mesh import make_production_mesh, PEAK_FLOPS_BF16
from repro.launch import hlo_analysis
from repro.models import transformer as T
from repro.runtime.train import (TRAIN_RULES, SERVE_RULES, make_train_step,
                                 init_state, train_shardings)
from repro.runtime.serve import serve_shardings, make_prefill_step, make_decode_step


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS: 6*N_active*D for train (D = tokens), 2*N_active per
    decoded token, plus exact-ish attention terms."""
    n_active = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len
    hd = cfg.resolved_head_dim
    attn_layers = [k for k in cfg.block_kinds() if k in ("attn", "swa")]

    def attn_flops_train():
        total = 0.0
        for k in attn_layers:
            w = cfg.local_window if k == "swa" else 0
            eff = min(w, S) if w else S
            # qk + pv, causal ~ S*eff/2 pairs, x3 for fwd+bwd
            total += 6.0 * B * cfg.n_heads * hd * S * (eff if w else S / 2) * 2
        return total

    if shape.kind == "train":
        return 6.0 * n_active * B * S + attn_flops_train()
    if shape.kind == "prefill":
        total = 2.0 * n_active * B * S
        for k in attn_layers:
            w = cfg.local_window if k == "swa" else 0
            eff = min(w, S) if w else S
            total += 2.0 * B * cfg.n_heads * hd * S * (eff if w else S / 2) * 2
        return total
    # decode: one token against a seq_len cache
    total = 2.0 * n_active * B
    for k in attn_layers:
        w = cfg.local_window if k == "swa" else 0
        skv = min(w, S) if w else S
        total += 4.0 * B * cfg.n_heads * hd * skv
    return total


_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def make_flags(cfg: ArchConfig, shape: ShapeConfig, *, moe_mode="mem",
               remat="full", attn_chunk=512, param_dtype="f32",
               opt_dtype="f32") -> T.RunFlags:
    if shape.kind == "train":
        # flash (custom-vjp blockwise) attention: no S^2 materialization in
        # either direction, no scan-residual stacking
        return T.RunFlags(param_dtype=_DTYPES[param_dtype],
                          opt_dtype=_DTYPES[opt_dtype], remat=remat,
                          moe_mode=moe_mode, distributed=True,
                          attn_impl="flash", attn_chunk=attn_chunk)
    # no-grad serving: blockwise pair-scan keeps 32k prefill in VMEM budget
    return T.RunFlags(param_dtype=jnp.bfloat16, remat="none",
                      moe_mode=moe_mode, distributed=True,
                      attn_impl="blockwise", attn_chunk=attn_chunk)


def _base_rules(shape: ShapeConfig, rules_train=None, rules_serve=None):
    """The sharding-rule table a cell lowers under — the single train-vs-
    serve dispatch both ``lower_cell`` and the feedback loop consult."""
    return dict((rules_train or TRAIN_RULES) if shape.kind == "train"
                else (rules_serve or SERVE_RULES))


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, flags: T.RunFlags,
               rules_train=None, rules_serve=None, comm_plan=None):
    """Returns (lowered, meta).  No device memory is allocated: all inputs
    are ShapeDtypeStructs.  ``comm_plan`` (optional CommPlan) reaches every
    collective site through the step factories."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        rules = _base_rules(shape, rules_train, rules_serve)
        step, state_sh, batch_sh = make_train_step(cfg, flags, mesh, rules,
                                                   batch_shape=(B, S),
                                                   comm_plan=comm_plan)
        state_specs = jax.eval_shape(
            lambda: init_state(jax.random.key(0), cfg, flags))
        batch_specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
        return fn.lower(state_specs, batch_specs), {"step": "train_step"}

    rules = _base_rules(shape, rules_train, rules_serve)
    params_specs = jax.eval_shape(
        lambda: T.init_params(jax.random.key(0), cfg, flags.param_dtype))
    param_sh, cache_sh, tok_sh = serve_shardings(cfg, mesh, B, S, rules,
                                                 flags.param_dtype)
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, flags, mesh, rules, comm_plan=comm_plan)
        tok_specs = jax.ShapeDtypeStruct((B, S), jnp.int32)
        fn = jax.jit(step, in_shardings=(param_sh, tok_sh))
        return fn.lower(params_specs, tok_specs), {"step": "prefill_step"}

    # decode: one new token against a pre-filled cache of seq_len
    step = make_decode_step(cfg, flags, mesh, rules, comm_plan=comm_plan)
    cache_specs = T.make_cache(cfg, B, S, flags.cache_dtype, as_specs=True)
    tok_specs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_specs = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(step, in_shardings=(param_sh, tok_sh, None, cache_sh),
                 out_shardings=(None, cache_sh), donate_argnums=(3,))
    return fn.lower(params_specs, tok_specs, pos_specs, cache_specs), \
        {"step": "serve_step"}


def build_comm_plan(policy: str, cfg: ArchConfig, shape: ShapeConfig, mesh,
                    hlo_text=None, noc_profile: str = "espsoc-3x4"):
    """Resolve a --comm-plan policy against a concrete mesh: ``manual``
    keeps the legacy flag-driven behaviour; ``auto`` prices the step's
    transfers with the NoC cost model (from the compiled module's own
    collectives when ``hlo_text`` is given; on the ``noc_profile`` link
    parameters — pod-scale profiles in configs.espsoc_trafficgen.PROFILES);
    ``mem``/``mcast`` are the constant baselines the benchmark compares
    against.  The rule-overlay feedback path goes through
    ``planner.refine_plan_from_hlo`` instead (see ``run_cell``)."""
    from repro.configs.espsoc_trafficgen import noc_model
    return resolve_policy(policy, cfg, shape, dict(mesh.shape),
                          hlo_text=hlo_text, model=noc_model(noc_profile))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             moe_mode: str = "mem", remat: str = "full",
             attn_chunk: int = 512, rules_train=None, rules_serve=None,
             param_dtype: str = "f32", opt_dtype: str = "f32",
             comm_plan: str = "manual", noc_profile: str = "espsoc-3x4",
             calibrate: bool = False, verbose: bool = True
             ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    plan, decisions = build_comm_plan(comm_plan, cfg, shape, mesh,
                                      noc_profile=noc_profile)
    if plan is not None and cfg.moe is not None:
        # keep the recorded moe_mode coherent with what the plan selects
        moe_mode = ("mem" if plan.mode("moe_dispatch") is CommMode.MEM
                    else "mcast")
    flags = make_flags(cfg, shape, moe_mode=moe_mode, remat=remat,
                       attn_chunk=attn_chunk, param_dtype=param_dtype,
                       opt_dtype=opt_dtype)
    t0 = time.monotonic()
    socket_mod.reset_issue_log()   # capture the *issued* modes of this trace
    lowered, meta = lower_cell(cfg, shape, mesh, flags, rules_train,
                               rules_serve, comm_plan=plan)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    # --comm-plan=auto phase 2: re-price from the *compiled* module's own
    # collective ops (ground truth for fan-out/bytes, one spec per layer),
    # then close the loop into the sharding rules: resolve_rules rewrites
    # the axis table from the per-layer plan (e.g. w_fsdp off when the
    # weight all-gather prices to MCAST).  If the rules changed or a mode
    # the step consults changed, relower ONCE with the resolved rules +
    # refined plan — no further feedback iteration (once-iff-changed).
    replanned = False
    overlay = {}
    cycles_static = cycles_resolved = cycles_serial = None
    overlap_frac = None
    replan_events = None
    if comm_plan == "auto" and plan is not None:
        from repro.configs.espsoc_trafficgen import noc_model
        from repro.core.sharding import resolve_rules
        base_rules = _base_rules(shape, rules_train, rules_serve)
        plan2, decisions2, rules_resolved, overlay, rebuild = \
            refine_plan_from_hlo(plan, cfg, shape, dict(mesh.shape),
                                 compiled.as_text(),
                                 lambda p: resolve_rules(p, base_rules),
                                 model=noc_model(noc_profile))
        cycles_static = modeled_step_cycles(decisions2, base_rules)
        cycles_resolved = modeled_step_cycles(decisions2, rules_resolved)
        # the overlap objective's win over serial compute-waits-for-comm
        # pricing, for the SAME decisions and resolved rules — plus the
        # fraction of comm cycles hidden behind the compute they feed
        cycles_serial = modeled_step_cycles(decisions2, rules_resolved,
                                            objective="serial")
        overlap_frac = comm_overlap_fraction(decisions2, rules_resolved)
        # every decision the HLO ground truth flipped vs the estimate plan
        # — the same machine-readable record the elastic re-mesh path
        # appends to FaultTolerantRunner.comm_replan_events
        from repro.core.planner import plan_decision_flips
        replan_events = [dict(f, cause="hlo_refine")
                         for f in plan_decision_flips(plan, plan2)]
        plan, decisions = plan2, decisions2
        if rebuild:
            replanned = True
            if overlay:
                if shape.kind == "train":
                    rules_train = rules_resolved
                else:
                    rules_serve = rules_resolved
            if cfg.moe is not None:
                moe_mode = ("mem" if plan.mode("moe_dispatch") is CommMode.MEM
                            else "mcast")
                flags = make_flags(cfg, shape, moe_mode=moe_mode, remat=remat,
                                   attn_chunk=attn_chunk,
                                   param_dtype=param_dtype,
                                   opt_dtype=opt_dtype)
            t0 = time.monotonic()
            # re-capture: the artifact reports the FINAL step's issued modes
            socket_mod.reset_issue_log()
            lowered, meta = lower_cell(cfg, shape, mesh, flags, rules_train,
                                       rules_serve, comm_plan=plan)
            compiled = lowered.compile()
            t_compile += time.monotonic() - t0

    # --calibrate: a calibration is a re-plan (symmetric with the re-mesh
    # and hlo_refine paths).  Fit the live profile's SoCParams from a
    # seeded flit-sim run of the standard experiment grid (self-check:
    # residual ~ the noise floor when the closed forms and the flit fabric
    # agree), then re-price plan entries from the socket's issued-vs-
    # planned trace; every flip lands in comm_replan_events with its own
    # cause, exactly like the hlo_refine events above.
    calibration = None
    if calibrate and comm_plan == "auto" and plan is not None:
        from repro.calib import fit as calib_fit, measure
        from repro.configs.espsoc_trafficgen import noc_model
        from repro.core.noc.perfmodel import SoCParams
        from repro.core.planner import refine_plan_from_measurements
        model = noc_model(noc_profile)
        params = model.p if model is not None else SoCParams()
        sim_obs = (measure.flit_sim_observations(params) +
                   measure.compute_observations(params))
        cp = calib_fit.fit_soc_params(sim_obs, base=params)
        issue_obs = measure.observations_from_issue_log(
            socket_mod.issue_observations(plan))
        plan, calib_flips = refine_plan_from_measurements(
            plan, issue_obs, decisions=decisions)
        calibration = cp.summary()
        replan_events = (replan_events or []) + calib_flips
        if verbose:
            print(f"--calibrate: residual={cp.residual:.5f} "
                  f"({len(sim_obs)} sim obs, {len(issue_obs)} issue obs, "
                  f"{len(calib_flips)} plan flips)")

    ma = compiled.memory_analysis()
    ma_peak = ma.peak_memory_in_bytes
    mf = model_flops(cfg, shape)
    roof = hlo_analysis.analyze(compiled, model_flops_total=mf,
                                n_chips=n_chips)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": meta["step"],
        "moe_mode": moe_mode if cfg.moe else None,
        "comm_plan": ({name: plan.mode(name).name
                       for name in plan.modes} if plan is not None else None),
        "comm_plan_policy": comm_plan,
        "comm_plan_hlo_refined": (replanned if comm_plan == "auto" else None),
        # decision flips between the estimate plan and the plan in force
        # after re-planning (HLO refine here; shrink_mesh recovery appends
        # its flips to the runner's comm_replan_events the same way)
        "comm_replan_events": (replan_events
                               if comm_plan == "auto" else None),
        # --calibrate: the CalibratedParams artifact (per-field fit
        # diagnostics) for this cell's NoC profile; None when not requested
        "calibration": calibration,
        # planner -> sharding feedback: the axis rules the plan rewrote
        # (e.g. {"w_fsdp": null} when weights broadcast on MCAST) and the
        # modeled step cost under static vs resolved rules
        "comm_rule_overlay": (overlay or None) if comm_plan == "auto" else None,
        "comm_plan_static_cycles": cycles_static,
        "comm_plan_resolved_cycles": cycles_resolved,
        # overlap objective: resolved-rule cycles under serial pricing
        # (compute waits for comm) vs the default overlapped pricing, and
        # the fraction of comm cycles hidden behind the compute they feed
        "comm_plan_serial_cycles": cycles_serial,
        "comm_overlap_fraction": overlap_frac,
        "comm_plan_layer_mix": (mode_mix(decisions)
                                if decisions is not None else None),
        # per-site *issued* modes from the socket's trace-time issue log:
        # what each migrated call site actually dispatched (vs planned) in
        # the step the artifact describes
        "comm_issued": socket_mod.issued_modes() or None,
        "comm_issued_matches_plan": (
            socket_mod.issued_matches_plan(plan) if plan is not None
            else None),
        "comm_plan_decisions": ([
            {"tensor": d.spec.name, "layer": d.spec.layer,
             "fan_out": d.spec.fan_out,
             "nbytes": d.spec.nbytes, "mode": d.mode.name,
             "speedup_vs_mem": round(d.speedup_vs_mem, 3),
             "fused": d.fused, "streamed": d.streamed,
             "compute_cycles": round(d.compute_cycles, 1),
             "reason": d.reason} for d in decisions]
            if decisions is not None else None),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_dev": ma.argument_size_in_bytes,
            "output_bytes_per_dev": ma.output_size_in_bytes,
            "temp_bytes_per_dev": ma.temp_size_in_bytes,
            "peak_bytes_per_dev": ma_peak,
            "alias_bytes_per_dev": ma.alias_size_in_bytes,
            # XLA's memory_analysis misses while-carried buffers (verified);
            # peak_bytes_est adds the deepest live while-carry chain.
            "peak_bytes_est_per_dev": roof.peak_bytes_est,
            "fits_16gb": bool(max(ma_peak, roof.peak_bytes_est) < 16e9),
        },
        "roofline": {
            "flops_per_dev": roof.flops_per_dev,
            "hbm_bytes_per_dev": roof.hbm_bytes_per_dev,
            "wire_bytes_per_dev": roof.wire_bytes_per_dev,
            "compute_s": roof.compute_s,
            "memory_s": roof.memory_s,
            "collective_s": roof.collective_s,
            "dominant": roof.dominant,
            "model_flops_total": mf,
            "model_flops_per_dev": roof.model_flops_per_dev,
            "useful_flops_ratio": roof.useful_flops_ratio,
            "roofline_fraction": roof.roofline_fraction(),
        },
        "collectives": roof.collectives,
    }
    if verbose:
        if comm_plan == "auto" and decisions is not None:
            mix = ",".join(f"{k}:{v}" for k, v in
                           result["comm_plan_layer_mix"].items())
            delta = (f"; step cycles {cycles_static:.0f} -> "
                     f"{cycles_resolved:.0f} "
                     f"({cycles_static / max(cycles_resolved, 1e-9):.2f}x)"
                     if overlay else "")
            print(f"[{result['mesh']}] {arch} x {shape_name}: comm-plan "
                  f"mix [{mix}] overlay={overlay or '{}'}{delta}")
            if cycles_serial is not None:
                print(f"[{result['mesh']}] {arch} x {shape_name}: overlap "
                      f"objective {cycles_serial:.3g} -> "
                      f"{cycles_resolved:.3g} cycles "
                      f"({cycles_serial / max(cycles_resolved, 1e-9):.2f}x "
                      f"vs serial; {overlap_frac:.1%} of comm hidden)")
            issued = result["comm_issued"] or {}
            sites = ",".join(f"{s}:{v['issued']}" for s, v in issued.items())
            print(f"[{result['mesh']}] {arch} x {shape_name}: issued "
                  f"[{sites}] matches_plan="
                  f"{result['comm_issued_matches_plan']}")
            if result["comm_issued_matches_plan"] is False:
                # name the offenders instead of silently recording the flag
                for mm in socket_mod.mismatched_sites(plan):
                    print(f"[{result['mesh']}] {arch} x {shape_name}: "
                          f"ISSUED != PLANNED at {mm['site']} "
                          f"({mm['tensor']}: planned {mm['planned']}, "
                          f"issued {mm['issued']})")
        r = result["roofline"]
        print(f"[{result['mesh']}] {arch} x {shape_name} ({meta['step']}): "
              f"compile {t_compile:.1f}s | "
              f"peak/dev ~{roof.peak_bytes_est/2**30:.2f} GiB | "
              f"compute {r['compute_s']*1e3:.2f}ms "
              f"memory {r['memory_s']*1e3:.2f}ms "
              f"collective {r['collective_s']*1e3:.2f}ms "
              f"-> {r['dominant']}-bound | useful-FLOPs "
              f"{r['useful_flops_ratio']:.2f} | roofline frac "
              f"{r['roofline_fraction']:.3f}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-mode", default="mem", choices=("mem", "mcast"))
    ap.add_argument("--comm-plan", default="manual",
                    choices=("manual", "auto", "mem", "mcast"),
                    help="communication-mode policy: 'manual' follows "
                         "--moe-mode; 'auto' lets the NoC cost model pick "
                         "per transfer; 'mem'/'mcast' force one mode "
                         "everywhere (benchmark baselines)")
    ap.add_argument("--noc-profile", default="espsoc-3x4",
                    help="NoC cost-model profile for --comm-plan=auto "
                         "(espsoc-3x4 | pod-8x8 | pod-16x16; see "
                         "configs.espsoc_trafficgen.PROFILES)")
    ap.add_argument("--calibrate", action="store_true",
                    help="with --comm-plan=auto: fit SoCParams from a "
                         "seeded flit-sim run, re-price plan entries from "
                         "the socket's issued-vs-planned trace, and record "
                         "the CalibratedParams artifact + plan flips in "
                         "the output (docs/calibration.md)")
    ap.add_argument("--remat", default="full",
                    choices=("none", "full", "save_collectives"))
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--param-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--opt-dtype", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        for arch, shape in cells:
            try:
                res = run_cell(arch, shape, multi_pod=multi_pod,
                               moe_mode=args.moe_mode, remat=args.remat,
                               attn_chunk=args.attn_chunk,
                               param_dtype=args.param_dtype,
                               opt_dtype=args.opt_dtype,
                               comm_plan=args.comm_plan,
                               noc_profile=args.noc_profile,
                               calibrate=args.calibrate)
            except Exception as e:  # a failing cell is a bug in the system
                failures.append((arch, shape, multi_pod, repr(e)))
                print(f"FAIL [{'2x16x16' if multi_pod else '16x16'}] "
                      f"{arch} x {shape}: {e!r}")
                continue
            tag = ("_" + args.tag) if args.tag else ""
            if args.comm_plan != "manual":
                tag = f"_{args.comm_plan}plan" + tag
            mode = f"_{res['moe_mode']}" if res.get("moe_mode") else ""
            fname = (f"{arch}_{shape}_{res.get('mesh', 'skip')}"
                     f"{mode}{tag}.json")
            with open(os.path.join(args.out, fname), "w") as f:
                json.dump(res, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f4 in failures:
            print("  ", f4)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
