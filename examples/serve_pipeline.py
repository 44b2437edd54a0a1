"""Continuous-batching serving on the engine, with the KV prefix moving
over a live stage axis — the paper's dataflow (1 producer, N consumers)
as a model-serving topology.

Part 1 drives :class:`repro.runtime.engine.ServeEngine` end to end: a
deterministic Poisson arrival trace is admitted into a single
continuously batched decode step over a paged block cache.  Every
admission's prefill->decode hand-off issues through the socket from the
``engine.kv_prefix`` descriptor; with no live stage axis inside the
engine's jit domain the write degrades to the MEM path *with a recorded
reason* — the issue log shows the transfer either way.  (The paged pools
are preallocated once by the engine's block layout: there is no per-call
cache repad, and leaf classification keys on the logical ``cache_axes``
names, never on shape coincidences.)

Part 2 replays the same descriptor on real tiles: 8 forced host devices
form the "stage" axis, rank 0 is the PREFILL producer and the engine's
registered decode consumers receive one admitted request's KV prefix by
MULTICAST (Fig. 1(c): one producer burst forked to N consumers, instead
of N reads from host memory).  Consumer ranks ride the LUT as *traced*
values, so retargeting a consumer mid-serve (``remap_consumer``) changes
where the burst lands without retracing.

  PYTHONPATH=src python examples/serve_pipeline.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.core import socket as socket_mod
from repro.runtime.engine import ServeEngine, poisson_trace


def main():
    cfg = get_reduced("qwen3-4b")
    S, GEN = 16, 8

    # ---- part 1: continuous batching over the paged cache ----------------
    eng = ServeEngine(cfg, prompt_len=S, max_new_tokens=GEN, n_slots=4,
                      block_size=8,
                      consumers=("decode1", "decode2", "decode3"))
    trace = poisson_trace(6, rate=0.8, prompt_len=S, vocab=cfg.vocab_size,
                          max_new_tokens=GEN, seed=7)
    t0 = time.monotonic()
    metrics = eng.run(trace)
    dt = time.monotonic() - t0

    print(f"engine: {metrics.n_requests} requests, "
          f"{metrics.total_new_tokens} tokens in {metrics.steps} batched "
          f"steps ({dt*1e3:.0f} ms wall)")
    print(f"  tokens/s={metrics.tokens_per_s:.1f}  "
          f"p50={metrics.p50_latency_s*1e3:.1f} ms  "
          f"p99={metrics.p99_latency_s*1e3:.1f} ms")
    for site, rec in socket_mod.issued_modes().items():
        print(f"  issued {site}: {rec['issued']} (user={rec['user_field']}, "
              f"impl={rec['impl']})")
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}, \
        eng.trace_counts
    assert eng.allocator.n_used == 0, "eviction must return every block"
    kv = socket_mod.issued_modes()["engine.kv_prefix@prefill"]
    assert kv["degraded_reason"], "no stage axis -> recorded degradation"
    gens = {r.rid: r.generated for r in eng.completed}
    assert len({tuple(g) for g in gens.values()}) > 1, \
        "distinct prompts should decode distinct continuations"

    # ---- part 2: the same descriptor on a live 8-tile stage axis ---------
    mesh = jax.make_mesh((8,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    writer = eng.make_stage_kv_writer("stage")
    # one admitted request's first-layer K prefix, as the burst payload
    leaf = jax.tree.leaves(eng.pools)[0]
    payload = np.zeros((8, leaf.size), np.float32)
    payload[0] = np.asarray(leaf, np.float32).reshape(-1)

    traces = []

    def burst(rows, ranks):
        traces.append(1)            # trace-time only: counts retraces
        return writer(rows, ranks)

    fn = jax.jit(jax.shard_map(
        burst, mesh=mesh, in_specs=(P("stage", None), P()),
        out_specs=P("stage", None), check_vma=False))

    out = np.asarray(fn(payload, eng.consumer_ranks()))
    for r in (1, 2, 3):
        np.testing.assert_allclose(out[r], payload[0])
    assert not out[6].any(), "rank 6 is not yet a consumer"

    eng.remap_consumer("decode3", 6)     # LUT update: retarget mid-serve
    out2 = np.asarray(fn(payload, eng.consumer_ranks()))
    for r in (1, 2, 6):
        np.testing.assert_allclose(out2[r], payload[0])
    assert not out2[3].any(), "rank 3 was remapped away"
    assert len(traces) == 1, f"stage burst retraced {len(traces)}x"

    rec = [r for r in socket_mod.issued_records()
           if r.site == "engine.kv_prefix"][-1]
    print(f"stage burst: issued {rec.issued} (user={rec.user}, "
          f"impl={rec.impl}) — remap retargeted rank 3 -> 6 with "
          f"{len(traces)} trace")
    print("ok: one multicast prefix burst, continuously batched decode, "
          "no retrace across remap.")


if __name__ == "__main__":
    main()
