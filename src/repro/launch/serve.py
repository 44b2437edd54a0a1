"""Serving driver: batched prefill + decode with the serve sharding rules.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
      --preset reduced --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.configs.espsoc_trafficgen import noc_model
from repro.core import socket as socket_mod
from repro.core.planner import (plan_summary_lines, refine_plan_from_hlo,
                                resolve_policy)
from repro.models import transformer as T
from repro.models.transformer import RunFlags
from repro.runtime.serve import (make_prefill_step, make_decode_step,
                                 grow_caches, resolved_serve_rules,
                                 serve_shardings)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh


def run_engine(args, cfg) -> int:
    """``--engine``: drive the continuous-batching ServeEngine over a
    deterministic Poisson trace and (with ``--artifact``) write the
    serve dryrun artifact the CI coverage gate cross-checks with
    ``python -m repro.analysis --against-artifact``."""
    import json

    from repro.core.planner import plan_summary_lines
    from repro.runtime.engine import ServeEngine, poisson_trace

    socket_mod.reset_issue_log()
    eng = ServeEngine(cfg, prompt_len=args.prompt_len,
                      max_new_tokens=args.gen, n_slots=args.batch,
                      block_size=args.block_size)
    trace = poisson_trace(args.requests, rate=args.rate,
                          prompt_len=args.prompt_len, vocab=cfg.vocab_size,
                          max_new_tokens=args.gen, seed=args.seed)
    metrics = eng.run(trace)
    for line in plan_summary_lines(eng.plan_decisions or ()):
        print(line)
    issued = socket_mod.issued_modes()
    mismatched = socket_mod.mismatched_sites(eng.plan)
    print(f"engine: arch={cfg.name} slots={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} "
          f"requests={metrics.n_requests}")
    print(f"  {metrics.total_new_tokens} tokens in {metrics.steps} steps: "
          f"{metrics.tokens_per_s:.1f} tok/s, "
          f"p50={metrics.p50_latency_s*1e3:.1f} ms, "
          f"p99={metrics.p99_latency_s*1e3:.1f} ms")
    print("comm-plan issued: " + ", ".join(
        f"{s}->{v['issued']}" for s, v in issued.items()))
    for mm in mismatched:
        print(f"comm-plan MISMATCH at {mm['site']}: {mm['tensor']} "
              f"planned {mm['planned']}, issued {mm['issued']}")
    if args.artifact:
        artifact = {
            "kind": "serve_engine", "arch": cfg.name,
            "shape": {"n_slots": args.batch, "prompt_len": args.prompt_len,
                      "max_new_tokens": args.gen,
                      "block_size": args.block_size},
            "metrics": metrics.summary(),
            "comm_plan": {k: v.name for k, v in eng.plan.modes.items()},
            "comm_issued": issued,
            "comm_issued_matches_plan": not mismatched,
            "trace_counts": eng.trace_counts,
        }
        with open(args.artifact, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        print(f"wrote {args.artifact}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_NAMES)
    ap.add_argument("--preset", default="reduced", choices=("reduced", "full"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="none", choices=("none", "single", "multi"))
    ap.add_argument("--comm-plan", default="manual",
                    choices=("manual", "auto", "mem", "mcast"),
                    help="per-transfer communication-mode policy (auto = "
                         "NoC cost model picks; see core.planner)")
    ap.add_argument("--noc-profile", default="espsoc-3x4",
                    help="NoC cost-model profile for --comm-plan=auto "
                         "(espsoc-3x4 | pod-8x8 | pod-16x16)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching ServeEngine over a "
                         "deterministic Poisson trace (paged KV cache)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="--engine: KV block size (must divide "
                         "prompt_len + gen)")
    ap.add_argument("--requests", type=int, default=8,
                    help="--engine: requests in the Poisson trace")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="--engine: Poisson arrival rate (req/step)")
    ap.add_argument("--seed", type=int, default=0,
                    help="--engine: arrival-trace seed")
    ap.add_argument("--artifact", default=None,
                    help="--engine: write the serve dryrun artifact JSON "
                         "here (CI cross-checks it with --against-artifact)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.preset == "full" else \
        get_reduced(args.arch)
    if args.engine:
        return run_engine(args, cfg)
    flags = RunFlags(param_dtype=jnp.bfloat16, remat="none")
    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
    mesh_axes = dict(mesh.shape) if mesh is not None else {}
    model = noc_model(args.noc_profile)
    plan, decisions = resolve_policy(args.comm_plan, cfg, shape, mesh_axes,
                                     model=model)
    prefill = None
    rules = None
    if args.comm_plan == "auto" and mesh is not None:
        # re-price from the compiled prefill step's own collective ops; in
        # the common no-replan case keep the compiled executable — no
        # second XLA compile
        params_specs = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg, flags.param_dtype))
        tok_specs = jax.ShapeDtypeStruct((args.batch, args.prompt_len),
                                         jnp.int32)
        socket_mod.reset_issue_log()
        compiled = jax.jit(make_prefill_step(cfg, flags, mesh,
                                             comm_plan=plan)) \
            .lower(params_specs, tok_specs).compile()
        # planner -> sharding feedback: re-price per layer from the
        # compiled HLO, rewrite the serve rule table (e.g. the
        # w_fsdp="data" gather dropped when weights broadcast on MCAST),
        # rebuild once iff changed
        plan, decisions, rules, overlay, rebuild = refine_plan_from_hlo(
            plan, cfg, shape, mesh_axes, compiled.as_text(),
            resolved_serve_rules, model=model)
        if rebuild:
            if overlay:
                print(f"comm-plan: rule overlay {overlay} applied; "
                      "rebuilding the steps")
            else:
                print("comm-plan: HLO-derived pricing changed the plan")
            # the rebuilt steps trace at their first call: drop the
            # discarded trace's records so the post-run issued summary
            # describes the steps that actually ran
            socket_mod.reset_issue_log()
        else:
            prefill = compiled
            rules = None   # no rebuild: keep the default serve rules
    for line in plan_summary_lines(decisions or ()):
        print(line)

    param_sh = None if mesh is None else serve_shardings(
        cfg, mesh, args.batch, args.prompt_len + args.gen, rules,
        flags.param_dtype)[0]
    params = T.init_params_on_device(jax.random.key(0), cfg,
                                     flags.param_dtype, param_sh)
    if prefill is None:
        prefill = jax.jit(make_prefill_step(cfg, flags, mesh, rules=rules,
                                            comm_plan=plan))
    decode = jax.jit(make_decode_step(cfg, flags, mesh, rules=rules,
                                      comm_plan=plan))

    B, S = args.batch, args.prompt_len
    total = S + args.gen
    prompts = jax.random.randint(jax.random.key(1), (B, S), 0,
                                 cfg.vocab_size)

    t0 = time.monotonic()
    logits, caches = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.monotonic() - t0

    # grow attention caches once to hold the generated tokens; leaves are
    # classified by logical axis names (runtime.serve.grow_caches), never
    # by shape coincidences
    caches = grow_caches(cfg, caches, S, args.gen)

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.monotonic()
    for i in range(args.gen - 1):
        logits, caches = decode(params, tok, jnp.int32(S + i), caches)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.monotonic() - t0

    gen = jnp.concatenate(out, axis=1)
    issued = socket_mod.issued_modes()
    if issued:
        print("comm-plan issued: " + ", ".join(
            f"{s}->{v['issued']}" for s, v in issued.items()))
        for mm in socket_mod.mismatched_sites(plan):
            print(f"comm-plan MISMATCH at {mm['site']}: {mm['tensor']} "
                  f"planned {mm['planned']}, issued {mm['issued']}")
    print(f"arch={cfg.name} batch={B} prompt={S} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({B*S/t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
