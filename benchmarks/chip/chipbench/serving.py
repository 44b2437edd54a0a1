"""Open-loop serving through the program's engine (``runtime.engine.ServeEngine``).

Requests are due on the wall clock, whatever the engine is doing; each is
handed to the engine at the first loop turn after it is due, and every time
is taken from when it was due.  A token counts as seen when the
``ServeEngine.step()`` call that produced it returns: that is where the
harness, like a streaming client, sees it.  One process, one thread.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correctness, flops, traffic, weights
from chipbench.harness import log, percentile


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    admitted: int
    decode_keys: List[int]        # keys attended by each token decoded


@dataclasses.dataclass
class ServeRun:
    w0: float
    w1: float
    steps: List[Step]                 # steps that ended inside the window
    tokens_in_window: int
    itl_s: List[float]
    ttft_s: List[float]               # requests due in the window
    attempted: int
    failed: int
    prompt_tokens: int


def build(cell, seed: int):
    """Weights from the seed and the engine over them, warmed up on the
    cell's shapes (one admission and one decode step)."""
    from repro.runtime.engine import ServeEngine
    ref, adapter = cell.reference(), cell.adapter()
    mix = cell.mix
    cfg = adapter.arch_config(cell.conf)
    specs = ref.weight_specs(cell.conf)
    make = weights.generator(
        specs, jnp.bfloat16,
        adapt=lambda canon: adapter.program_params(canon, cell.conf))
    params = make(seed)
    jax.block_until_ready(params)
    out_max = _max_out(mix)
    eng = ServeEngine(cfg, prompt_len=int(mix["prompt_tokens"]),
                      max_new_tokens=out_max, n_slots=int(mix["slots"]),
                      block_size=int(mix["block_size"]), params=params)
    warm = np.zeros(int(mix["prompt_tokens"]), np.int32)
    eng.submit(warm, 2, rid=-1)
    while eng.pending or eng.n_active:
        eng.step()
    eng.completed.clear()
    return eng


def _max_out(mix) -> int:
    o = mix["output_tokens"]
    return int(o) if isinstance(o, int) else int(o["max"])


def drive(cell, eng, seed: int, seconds: float, t_start: float,
          tracer=None, rate: Optional[float] = None
          ) -> ServeRun:
    """Offer the mix's load from ``t_start``; the window opens after the
    mix's ``warmin_s`` and lasts ``seconds``.  With ``drain_s`` the loop
    goes on (load still offered) until every request due in the window has
    its first token, for at most that long; one that has none by then
    counts an infinite time to first token."""
    mix = cell.mix
    vocab = cell.reference().dims(cell.conf)["V"]
    P = int(mix["prompt_tokens"])
    n_slots = int(mix["slots"])
    warmin, drain = float(mix["warmin_s"]), float(mix.get("drain_s", 0))
    reqs = traffic.open_loop(mix, seed, vocab, warmin + seconds + drain + 5,
                             rate)
    w0 = t_start + warmin
    w1 = w0 + seconds
    due = [t_start + r.due_s for r in reqs]
    nxt = 0
    inflight: Dict[int, object] = {}      # rid -> engine Request
    seen: Dict[int, int] = {}
    tok_times: Dict[int, List[float]] = {}
    steps: List[Step] = []
    lateness: List[float] = []
    n_in_window = 0
    itl: List[float] = []
    while True:
        now = time.perf_counter()
        if tracer:
            tracer.maybe_stop(now)
            tracer.maybe_start(now, w0)
        while nxt < len(reqs) and due[nxt] <= now:
            r = reqs[nxt]
            inflight[nxt] = eng.submit(r.prompt, r.max_new_tokens, rid=nxt)
            seen[nxt] = 0
            tok_times[nxt] = []
            lateness.append(now - due[nxt])
            nxt += 1
        if now >= w1:
            waiting = [i for i, t in enumerate(due)
                       if w0 <= t < w1 and not tok_times.get(i)]
            if not waiting or now >= w1 + drain:
                break
        if eng.pending or eng.n_active:
            admit = bool(eng.pending) and eng.n_active < n_slots
            with jax.profiler.TraceAnnotation(
                    "engine.step.admit" if admit else "engine.step.decode"):
                t0 = time.perf_counter()
                info = eng.step()
                t1 = time.perf_counter()
            keys = []
            done = []
            for rid, req in inflight.items():
                n = len(req.generated)
                if n > seen[rid]:
                    for j in range(max(seen[rid], 1), n):
                        keys.append(P + j)
                    tt = tok_times[rid]
                    if w0 <= t1 < w1:
                        n_in_window += n - seen[rid]
                        if tt:
                            itl.append(t1 - tt[-1])
                        itl.extend([0.0] * (n - seen[rid] - 1))
                    tt.extend([t1] * (n - seen[rid]))
                    seen[rid] = n
                if req.done:
                    done.append(rid)
            for rid in done:
                del inflight[rid]
            if w0 <= t1 < w1:
                steps.append(Step(t0, t1, info["admitted"], keys))
        else:
            nap = (due[nxt] if nxt < len(due) else w1) - time.perf_counter()
            with jax.profiler.TraceAnnotation("traffic.wait"):
                if nap > 0:
                    time.sleep(min(nap, 0.05))
    if tracer:
        tracer.maybe_stop(time.perf_counter(), force=True)
    in_window = [i for i, t in enumerate(due[:nxt]) if w0 <= t < w1]
    ttft = [(tok_times[i][0] - due[i]) if tok_times[i] else math.inf
            for i in in_window]
    log(f"generator: {nxt} requests offered, lateness p50 "
        f"{percentile(lateness, 50) * 1e3:.3f} ms, p95 "
        f"{percentile(lateness, 95) * 1e3:.3f} ms, max "
        f"{max(lateness, default=0) * 1e3:.3f} ms; queued at the close "
        f"{len(eng.pending)}, active {eng.n_active}")
    # with a drain, a request due in the window that never got its first
    # token failed; without one (above the knee) it is still queued
    failed = sum(t == math.inf for t in ttft) if drain > 0 else 0
    return ServeRun(w0=w0, w1=w1, steps=steps, tokens_in_window=n_in_window,
                    itl_s=itl, ttft_s=ttft, attempted=len(in_window),
                    failed=failed, prompt_tokens=P)


def sample_finished(cell, eng, seed: int) -> dict:
    """Requests to check, drawn from the seed among those finished: the
    longest and ``check.requests - 1`` others.  Padded sequences of
    prompt and served tokens, in the reference's shape."""
    mix = cell.mix
    P, s_max = int(mix["prompt_tokens"]), int(mix["prompt_tokens"]) + \
        _max_out(mix)
    n = int(cell.checks["requests"])
    done = sorted((r for r in eng.completed if r.rid >= 0),
                  key=lambda r: (-len(r.generated), r.rid))
    if not done:
        return {"n": 0}
    rng = np.random.default_rng(seed ^ 0x5EED)
    rest = done[1:]
    pick = [done[0]] + [rest[i] for i in sorted(
        rng.choice(len(rest), min(n - 1, len(rest)), replace=False))]
    pick += [done[0]] * (n - len(pick))
    tokens = np.zeros((n, s_max), np.int32)
    nexts = np.zeros((n, s_max), np.int32)
    mask = np.zeros((n, s_max), bool)
    for i, r in enumerate(pick):
        g = np.asarray(r.generated, np.int32)
        seq = np.concatenate([r.prompt, g[:-1]])
        tokens[i, :len(seq)] = seq
        nexts[i, P - 1:P - 1 + len(g)] = g
        mask[i, P - 1:P - 1 + len(g)] = True
    return {"n": len({r.rid for r in pick}), "tokens": tokens,
            "next": nexts, "mask": mask,
            "served": int(sum(len(r.generated) for r in
                              {r.rid: r for r in pick}.values()))}


def check(cell, seed: int, sample: dict, control: bool = False) -> Dict:
    """The readings compared against the cell's limits, by the plain
    reference (weights drawn again from the seed).  Call with the
    program's state freed."""
    if not sample.get("n"):
        return {"served_gap": None}
    gc.collect()
    ref = cell.reference()
    w = weights.generator(ref.weight_specs(cell.conf), jnp.bfloat16)(seed)
    fn = jax.jit(lambda w, t, x, m: correctness.served_gaps(
        ref, w, cell.conf, t, x, m, control=control))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(fn(w, sample["tokens"], sample["next"],
                             sample["mask"]))
    del w
    return {"served_gap": float(gaps.max())}


def metrics_e2e(run: ServeRun, seconds: float) -> Dict[str, float]:
    return {"output_tok_s": run.tokens_in_window / seconds,
            "itl_p95_ms": percentile(run.itl_s, 95) * 1e3,
            "ttft_p95_ms": percentile(run.ttft_s, 95) * 1e3}


def layer_inputs(cell, run: ServeRun) -> Dict:
    """What the per-layer readers of a serving cell read."""
    ref = cell.reference()
    m = ref.dims(cell.conf)
    return {"steps": run.steps, "w0": run.w0, "w1": run.w1,
            "prompt_tokens": run.prompt_tokens, "dims": m,
            "weight_bytes": flops.weight_bytes(ref.weight_specs(cell.conf)),
            "kv_bytes_per_token": flops.kv_bytes_per_token(m)}
