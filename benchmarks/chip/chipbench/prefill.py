"""Closed-loop sharded prefill through the program's step factory
(``runtime.serve.make_prefill_step``) on a (data=1, model=chips) mesh.

The MoE dispatch mode is the ``moe_dispatch`` verdict of the plan that
``core.planner.resolve_policy("auto", ...)`` builds for this shape and
mesh.  Each step prefills one batch of prompts and returns its first
tokens to the host; the next batch goes in when they are back.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correctness, weights
from chipbench.harness import log


@dataclasses.dataclass
class PrefillRun:
    w0: float
    t_end: float
    steps: List[tuple]            # (t0, t1) of each step in the window
    tokens_per_step: int
    sample: dict


class Prefill:
    """Weights, the compiled step and the prompt batches of one seed."""

    def __init__(self, cell, seed: int):
        from repro.configs.base import ShapeConfig
        from repro.core.planner import resolve_policy
        from repro.launch.hlo_analysis import collective_op_details
        from repro.launch.mesh import make_device_mesh
        from repro.models import transformer as T
        from repro.runtime.serve import make_prefill_step, serve_shardings
        from repro.runtime.train import SERVE_RULES

        mix, conf = cell.mix, cell.conf
        ref, adapter = cell.reference(), cell.adapter()
        self.cell = cell
        self.B, self.P = int(mix["batch"]), int(mix["prompt_tokens"])
        cfg = adapter.arch_config(conf)
        self.mesh = make_device_mesh(model=cell.chips,
                                     devices=jax.devices()[:cell.chips])
        shape = ShapeConfig(f"prefill_{self.P}", self.P, self.B, "prefill")
        plan, decisions = resolve_policy("auto", cfg, shape,
                                         dict(self.mesh.shape))
        for d in decisions or []:
            log(f"plan: {d.spec.name} -> {d.mode.name} ({d.reason})")
        self.moe_mode = plan.mode("moe_dispatch").name
        flags = T.RunFlags(compute_dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16, remat="none",
                           distributed=True,
                           attn_impl=conf["run"]["attn_impl"])
        param_sh, _, tok_sh = serve_shardings(cfg, self.mesh, self.B, self.P,
                                              SERVE_RULES, jnp.bfloat16)
        make = weights.generator(
            ref.weight_specs(conf), jnp.bfloat16,
            adapt=lambda canon: adapter.program_params(canon, conf),
            shardings=param_sh)
        self.params = make(seed)
        vocab = ref.dims(conf)["V"]
        batch = jax.jit(
            lambda words, i: jax.random.randint(
                jax.random.fold_in(jax.random.fold_in(
                    jax.random.key(words[0]), words[1]), i),
                (self.B, self.P), 0, vocab, jnp.int32),
            out_shardings=tok_sh)
        words = jnp.asarray(weights.seed_words(seed ^ 0x7E57))
        self.batches = [batch(words, jnp.int32(i))
                        for i in range(int(mix["batches"]))]
        step = jax.jit(make_prefill_step(cfg, flags, self.mesh, SERVE_RULES,
                                         plan),
                       in_shardings=(param_sh, tok_sh))
        self.step = step.lower(self.params, self.batches[0]).compile()
        colls = collections.Counter(
            d["kind"] for d in collective_op_details(self.step.as_text()))
        log(f"moe_dispatch verdict {self.moe_mode}; compiled prefill "
            f"collectives {dict(sorted(colls.items()))}")
        self.first = jax.jit(lambda lg: jnp.argmax(lg[:, -1], -1))
        logits, caches = self.step(self.params, self.batches[0])
        np.asarray(self.first(logits))
        self.kv_of = jax.jit(lambda c: (c["groups"]["b0"]["k"][-1],
                                        c["groups"]["b0"]["v"][-1]))
        jax.block_until_ready(self.kv_of(caches))
        del logits, caches

    def drive(self, seed: int, seconds: float, t_start: float,
              tracer=None) -> PrefillRun:
        """Steps back to back from ``t_start`` for ``seconds``; the steps
        named by ``check.steps`` (drawn from the seed among the first
        ``check.steps_from``) keep their first tokens and caches."""
        chk = self.cell.checks
        rng = np.random.default_rng(seed ^ 0x5EED)
        keep = set(int(i) for i in rng.choice(
            int(chk["steps_from"]), int(chk["steps"]), replace=False))
        w0, w1 = t_start, t_start + seconds
        steps, kept, i = [], {}, 0
        while True:
            now = time.perf_counter()
            if tracer:
                tracer.maybe_stop(now)
                tracer.maybe_start(now, w0)
            if now >= w1:
                break
            b = i % len(self.batches)
            with jax.profiler.TraceAnnotation("prefill.step"):
                t0 = time.perf_counter()
                logits, caches = self.step(self.params, self.batches[b])
                first = np.asarray(self.first(logits))
                t1 = time.perf_counter()
            steps.append((t0, t1))
            if i in keep:
                kept[i] = (b, first, caches)
            del logits, caches
            i += 1
        if tracer:
            tracer.maybe_stop(time.perf_counter(), force=True)
        t_end = steps[-1][1] if steps else w1
        missing = keep - set(kept)
        if missing:
            log(f"check: steps {sorted(missing)} never ran")
        log(f"prefill: {len(steps)} steps of {self.B} x {self.P} tokens")
        return PrefillRun(w0=w0, t_end=t_end, steps=steps,
                          tokens_per_step=self.B * self.P,
                          sample={"kept": kept, "missing": len(missing)})

    def sample_for_check(self, run: PrefillRun) -> dict:
        """Host copies of what the check reads; frees the kept caches."""
        toks, firsts, ks, vs = [], [], [], []
        for i in sorted(run.sample["kept"]):
            b, first, caches = run.sample["kept"][i]
            k, v = self.kv_of(caches)
            toks.append(np.asarray(self.batches[b]))
            firsts.append(first)
            ks.append(np.asarray(k))
            vs.append(np.asarray(v))
        run.sample["kept"].clear()
        if not toks:
            return {"n": 0}
        return {"n": len(toks) * self.B, "tokens": np.concatenate(toks),
                "first": np.concatenate(firsts),
                "k": np.concatenate(ks), "v": np.concatenate(vs),
                "missing": run.sample["missing"]}

    def close(self):
        del self.params, self.batches, self.step
        gc.collect()


def check(cell, seed: int, sample: dict, control: bool = False) -> Dict:
    """The reference (weights drawn again from the seed, experts over the
    same chips) on every checked prompt."""
    if not sample.get("n") or sample.get("missing"):
        return {"first_token_gap": None, "kv_rel_err": None}
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    ref = cell.reference()
    specs = ref.weight_specs(cell.conf)
    mesh = Mesh(np.asarray(jax.devices()[:cell.chips]), ("e",))
    # experts over the chips as the program holds them, the vocabulary
    # too (a replicated float32 copy of the head would not fit), the rest
    # whole on each chip
    split = {"layers/w_gate": P(None, "e"), "layers/w_up": P(None, "e"),
             "layers/w_down": P(None, "e"), "embed": P("e"),
             "unembed": P("e")}
    sh = {}
    for name in specs:
        spec = split.get(name, P())
        parts = name.split("/")
        (sh.setdefault(parts[0], {}) if len(parts) == 2 else sh)[
            parts[-1]] = NamedSharding(mesh, spec)
    w = weights.generator(specs, jnp.bfloat16, shardings=sh)(seed)
    fn = jax.jit(lambda w, t, f, k, v: correctness.prefill_readings(
        ref, w, cell.conf, t, f, (k, v), control=control))
    with jax.default_matmul_precision("highest"):
        gaps, rel = fn(w, sample["tokens"], sample["first"], sample["k"],
                       sample["v"])
    gaps, rel = np.asarray(gaps), np.asarray(rel)
    del w
    log(f"check: first-token gaps {np.round(gaps, 5).tolist()}, "
        f"kv rel err {np.round(rel, 6).tolist()}")
    return {"first_token_gap": float(gaps.max()),
            "kv_rel_err": float(rel.max())}


def metrics_e2e(run: PrefillRun) -> Dict[str, float]:
    span = run.t_end - run.w0
    return {"prefill_tok_s": len(run.steps) * run.tokens_per_step / span}


def layer_inputs(cell, run: PrefillRun) -> Dict:
    ref = cell.reference()
    m = ref.dims(cell.conf)
    return {"prefill_steps": run.steps, "w0": run.w0, "w1": run.t_end,
            "batch": int(cell.mix["batch"]),
            "prompt_tokens": int(cell.mix["prompt_tokens"]), "dims": m}
