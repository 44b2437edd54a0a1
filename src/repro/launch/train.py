"""End-to-end training driver with fault tolerance.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --preset reduced --steps 300 --ckpt /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --preset full --global-batch 8 --seq 512 --steps 100

On real hardware the same driver runs under the production mesh: pass
--mesh single|multi to shard with make_production_mesh (requires the
matching device count; on this CPU container use the default --mesh none).
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.configs.espsoc_trafficgen import noc_model
from repro.core import socket as socket_mod
from repro.core.planner import (plan_summary_lines, refine_plan_from_hlo,
                                resolve_policy)
from repro.data import SyntheticTokenStream
from repro.models.transformer import RunFlags
from repro.runtime.fault import (FaultTolerantRunner, FaultError,
                                 replan_for_mesh, shrink_mesh)
from repro.runtime.train import (make_train_step, init_state,
                                 resolved_train_rules)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_NAMES)
    ap.add_argument("--preset", default="reduced", choices=("reduced", "full"))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="none", choices=("none", "single", "multi"))
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a node failure at this step (demo)")
    ap.add_argument("--elastic-drop", type=int, default=0,
                    help="with --inject-failure-at: treat the failure as "
                         "losing this many devices — shrink_mesh onto the "
                         "survivors, re-plan the comm modes on the new "
                         "topology (re-mesh => re-plan), rebuild the step, "
                         "and restore onto it")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--comm-plan", default="manual",
                    choices=("manual", "auto", "mem", "mcast"),
                    help="per-transfer communication-mode policy (auto = "
                         "NoC cost model picks; see core.planner)")
    ap.add_argument("--noc-profile", default="espsoc-3x4",
                    help="NoC cost-model profile for --comm-plan=auto "
                         "(espsoc-3x4 | pod-8x8 | pod-16x16)")
    ap.add_argument("--calibrate", action="store_true",
                    help="after the run: fit SoCParams from flit-sim "
                         "timings, re-price the plan from the issued "
                         "record (a calibration is a re-plan; see "
                         "docs/calibration.md)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.preset == "full" else \
        get_reduced(args.arch)
    flags = RunFlags(remat="none" if args.preset == "reduced" else "full")
    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    shape = ShapeConfig("train_cli", args.seq, args.global_batch, "train")
    mesh_axes = dict(mesh.shape) if mesh is not None else {}
    model = noc_model(args.noc_profile)
    plan, decisions = resolve_policy(args.comm_plan, cfg, shape, mesh_axes,
                                     model=model)

    step_fn, state_sh, _ = make_train_step(
        cfg, flags, mesh, lr=args.lr, total_steps=args.steps,
        batch_shape=(args.global_batch, args.seq), comm_plan=plan)
    jstep = jax.jit(step_fn, donate_argnums=0)

    if args.comm_plan == "auto" and mesh is not None:
        # price from the compiled step's own collectives (fan-out/bytes from
        # the lowered ops, not the config estimates); rebuild the step only
        # if the refined plan disagrees, else run the already-compiled
        # executable — no second XLA compile
        state_specs = jax.eval_shape(
            lambda: init_state(jax.random.key(0), cfg, flags))
        batch_specs = {
            "tokens": jax.ShapeDtypeStruct(
                (args.global_batch, args.seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct(
                (args.global_batch, args.seq), jnp.int32),
        }
        socket_mod.reset_issue_log()
        compiled = jstep.lower(state_specs, batch_specs).compile()
        # planner -> sharding feedback: re-price per layer from the
        # compiled HLO, rewrite the rule table (e.g. w_fsdp off when
        # weights broadcast on MCAST), rebuild the step once iff changed
        plan, decisions, rules, overlay, rebuild = refine_plan_from_hlo(
            plan, cfg, shape, mesh_axes, compiled.as_text(),
            resolved_train_rules, model=model)
        if rebuild:
            if overlay:
                print(f"comm-plan: rule overlay {overlay} applied; "
                      "rebuilding the step")
            else:
                print("comm-plan: HLO-derived pricing changed the plan; "
                      "rebuilding the step")
            step_fn, state_sh, _ = make_train_step(
                cfg, flags, mesh, rules=rules, lr=args.lr,
                total_steps=args.steps,
                batch_shape=(args.global_batch, args.seq), comm_plan=plan)
            jstep = jax.jit(step_fn, donate_argnums=0)
            # the rebuilt step traces at its first call: drop the
            # discarded step's issue records so the post-run issued
            # summary describes the step that actually ran
            socket_mod.reset_issue_log()
        else:
            jstep = compiled
    for line in plan_summary_lines(decisions or ()):
        print(line)
    # one compiled program: leaves are born cast (and, on a mesh, sharded)
    state = jax.jit(functools.partial(init_state, cfg=cfg, flags=flags),
                    out_shardings=state_sh)(jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.global_batch}x{args.seq}")

    stream = SyntheticTokenStream(cfg.vocab_size, args.global_batch, args.seq)
    batches = lambda s: {k: jnp.asarray(v) for k, v in stream.batch(s).items()}

    remesh_hook = None
    if args.elastic_drop > 0 and mesh is not None:
        def remesh_hook(at_step, err):
            nonlocal mesh, plan, decisions
            survivors = list(mesh.devices.flat)[: -args.elastic_drop]
            model_parallel = dict(mesh.shape).get("model", 1)
            new_mesh = shrink_mesh(survivors, model_parallel)
            new_axes = dict(new_mesh.shape)
            # re-mesh => re-plan: re-price on the survivor topology and
            # re-resolve the rule overlay; with --comm-plan=auto, refine
            # from the relowered step's own HLO (same feedback loop as
            # launch, now inside the recovery path)
            new_plan, new_dec, rules, _, flips = replan_for_mesh(
                plan, cfg, shape, new_axes, resolve=resolved_train_rules,
                model=model)
            sfn, sh, _ = make_train_step(
                cfg, flags, new_mesh, rules=rules, lr=args.lr,
                total_steps=args.steps,
                batch_shape=(args.global_batch, args.seq),
                comm_plan=new_plan)
            jfn = jax.jit(sfn, donate_argnums=0)
            if args.comm_plan == "auto":
                state_specs = jax.eval_shape(
                    lambda: init_state(jax.random.key(0), cfg, flags))
                batch_specs = {
                    k: jax.ShapeDtypeStruct(
                        (args.global_batch, args.seq), jnp.int32)
                    for k in ("tokens", "labels")}
                hlo = jfn.lower(state_specs, batch_specs).compile().as_text()
                ref_plan, new_dec, rules, _, flips = replan_for_mesh(
                    plan, cfg, shape, new_axes,
                    hlo_text=hlo, resolve=resolved_train_rules, model=model)
                if any(ref_plan.mode(k) is not new_plan.mode(k)
                       for k in new_plan.modes):
                    sfn, sh, _ = make_train_step(
                        cfg, flags, new_mesh, rules=rules, lr=args.lr,
                        total_steps=args.steps,
                        batch_shape=(args.global_batch, args.seq),
                        comm_plan=ref_plan)
                    jfn = jax.jit(sfn, donate_argnums=0)
                new_plan = ref_plan
            mesh, plan, decisions = new_mesh, new_plan, new_dec
            print(f"!! re-mesh at step {at_step}: {new_mesh.size + args.elastic_drop}"
                  f" -> {new_mesh.size} devices, "
                  f"{len(flips)} comm decision(s) flipped")
            for f in flips:
                print(f"!! re-plan flip: {f['tensor']} "
                      f"{f['old']} -> {f['new']}")
            return {"step_fn": jfn, "shardings": sh, "flips": flips,
                    "mesh_axes": new_axes}

    runner = FaultTolerantRunner(jstep, args.ckpt,
                                 ckpt_every=args.ckpt_every,
                                 remesh_hook=remesh_hook)
    if args.inject_failure_at >= 0:
        fails = {args.inject_failure_at}

        def inject(step):
            if step in fails:
                fails.discard(step)
                print(f"!! injected node failure at step {step}")
                raise FaultError("injected")

        runner.inject_failures(inject)

    t0 = time.monotonic()
    state, hist = runner.run(state, batches, args.steps)
    dt = time.monotonic() - t0
    issued = socket_mod.issued_modes()
    if issued:
        print("comm-plan issued: " + ", ".join(
            f"{s}->{v['issued']}" for s, v in issued.items()))
        for mm in socket_mod.mismatched_sites(plan):
            print(f"comm-plan MISMATCH at {mm['site']}: {mm['tensor']} "
                  f"planned {mm['planned']}, issued {mm['issued']}")
    if args.calibrate and plan is not None:
        # plan -> measure -> re-plan: fit the timing constants from
        # flit-sim ground truth on this profile's fabric, then re-price
        # the plan against what the sockets actually issued — each flip
        # lands in the same comm_replan_events schema as a re-mesh
        from repro.calib import fit as calib_fit, measure
        from repro.core.noc.perfmodel import SoCParams
        from repro.core.planner import refine_plan_from_measurements
        params = model.p if model is not None else SoCParams()
        cp = calib_fit.fit_soc_params(
            measure.flit_sim_observations(params) +
            measure.compute_observations(params), base=params)
        obs = measure.observations_from_issue_log(
            socket_mod.issue_observations(plan))
        plan, calib_flips = refine_plan_from_measurements(
            plan, obs, decisions=decisions)
        print(f"calibrate: {params.name} -> {cp.params.name} "
              f"residual={cp.residual:.5f} n_obs={cp.n_obs}, "
              f"{len(calib_flips)} plan flip(s)")
        for f in calib_flips:
            print(f"calibrate flip: {f['tensor']} {f['old']} -> {f['new']} "
                  f"({f['cause']})")
    for h in hist:
        if h["step"] % args.log_every == 0 or h["step"] == args.steps - 1:
            print(f"step {h['step']:5d} loss {h['loss']:.4f} "
                  f"dt {h['dt']*1e3:.0f}ms"
                  + (" [straggler]" if h["straggler"] else ""))
    tok_s = args.steps * args.global_batch * args.seq / dt
    print(f"done: {args.steps} steps in {dt:.1f}s ({tok_s:.0f} tok/s), "
          f"restarts={runner.restarts}, "
          f"re-mesh events={len(runner.comm_replan_events)}, "
          f"stragglers={runner.straggler.events}, "
          f"final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
