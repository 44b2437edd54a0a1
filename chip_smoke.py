"""Smoke run of the serving path on a TPU, through the normal entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host of four chips

One chip: the full-width qwen3-4b model (36 layers, bf16 weights from a
seed) serves a seeded Poisson trace of 8 requests through
``ServeEngine``; every request must finish with its 32 tokens, and each
request's first token must equal the argmax of a direct prefill call on
the same weights and prompt.  The two single-device kernels then run
against their ``kernels/ref.py`` oracles.

Four chips (``--four-chips``, and nothing else): dbrx-132b at published
widths, cut to 2 layers, runs its sharded prefill on a (data=1, model=4)
mesh under both MoE dispatch modes, and the two sets of logits must agree;
then the ring all-gather matmul, ring reduce-scatter matmul and multicast
kernels run on the four chips against their ``lax`` counterparts.

Times printed on the way are smoke-run observations, not benchmark
metrics.  The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``, printed only when every check passed;
the script exits nonzero when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.streamed_gather import streamed_gather_matmul  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.hlo_analysis import collective_op_details  # noqa: E402
from repro.launch.mesh import make_device_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.engine import ServeEngine, poisson_trace  # noqa: E402
from repro.runtime.serve import make_prefill_step, serve_shardings  # noqa: E402
from repro.runtime.train import SERVE_RULES  # noqa: E402

# one-chip serving run
SERVE_ARCH = "qwen3-4b"
N_SLOTS, PROMPT_LEN, NEW_TOKENS, BLOCK_SIZE = 4, 128, 32, 16
N_REQUESTS, RATE, SEED = 8, 0.5, 0

# four-chip sharded prefill
MOE_ARCH, MOE_LAYERS, MOE_BATCH, MOE_PROMPT = "dbrx-132b", 2, 4, 256
# mem and mcast compute the same function.  They differ in summation
# order, in how GSPMD partitions the layers around the dispatch, and in
# the mem path's bf16 combine psum.  In bf16 compute those roundings move
# the router's inputs, and top-k routing can turn that into larger
# changes (1.9e-2 of the largest logit on a TPU v5e), so the bf16 run is
# checked for finite logits only.  In float32 compute only the bf16
# combine remains: there the logits must agree to 4 bf16 ulps of their
# largest magnitude (max |mem - mcast| / max |mem|).
MOE_LOGITS_REL_BOUND = 4 * 2.0 ** -8

# kernel shape (m, k, n): per-shard rows, contraction, output columns
KERNEL_SHAPE = (512, 256, 512)
# kernel vs its lax counterpart or oracle: the same f32-accumulated dot,
# rounded to bf16 after a possibly different accumulation order — within
# 2 bf16 ulps of the largest magnitude
KERNEL_REL_BOUND = 2 * 2.0 ** -8

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and the persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite values")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Times a phase: wall seconds and the backend compile inside them."""
    t = types.SimpleNamespace(wall=0.0, compile=0.0)
    t0, c0 = time.perf_counter(), clock.seconds
    yield t
    t.wall = time.perf_counter() - t0
    t.compile = clock.seconds - c0
    log(f"[{name}] wall {t.wall:.3f} s, of which backend compile "
        f"{t.compile:.3f} s")


# ------------------------------------------------------------ one chip ----

def serve_qwen(clock: CompileClock) -> None:
    cfg = get_config(SERVE_ARCH)
    with phase("init", clock):
        eng = ServeEngine(cfg, prompt_len=PROMPT_LEN,
                          max_new_tokens=NEW_TOKENS, n_slots=N_SLOTS,
                          block_size=BLOCK_SIZE, seed=SEED)
        jax.block_until_ready(eng.params)
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    log(f"{cfg.name}: {n_params / 1e9:.3f} B parameters, "
        f"{cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}")

    trace = poisson_trace(N_REQUESTS, rate=RATE, prompt_len=PROMPT_LEN,
                          vocab=cfg.vocab_size, max_new_tokens=NEW_TOKENS,
                          seed=SEED)
    with phase("serve", clock) as ph:
        metrics = eng.run(trace)
    serve_s = ph.wall - ph.compile
    log(f"served {metrics.n_requests} requests, "
        f"{metrics.total_new_tokens} tokens in {metrics.steps} engine "
        f"steps; excluding compile {serve_s:.3f} s = "
        f"{metrics.total_new_tokens / serve_s:.1f} tokens/s")

    check(metrics.n_requests == N_REQUESTS,
          f"{metrics.n_requests} of {N_REQUESTS} requests completed")
    for req in eng.completed:
        check(len(req.generated) == NEW_TOKENS,
              f"request {req.rid}: {len(req.generated)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in req.generated),
              f"request {req.rid}: token id out of range")
    check(all(bool(jnp.isfinite(leaf).all())
              for leaf in jax.tree.leaves(eng.pools)),
          "non-finite values in the KV block pools")

    with phase("reference prefill", clock):
        prefill = jax.jit(make_prefill_step(cfg, eng.flags))
        for req in sorted(eng.completed, key=lambda r: r.rid):
            logits, _ = prefill(eng.params, jnp.asarray(req.prompt)[None])
            check(bool(jnp.isfinite(logits).all()),
                  f"request {req.rid}: non-finite prefill logits")
            want = int(jnp.argmax(logits[0, -1]))
            check(req.generated[0] == want,
                  f"request {req.rid}: engine's first token "
                  f"{req.generated[0]} != direct prefill argmax {want}")
    log(f"first tokens match direct prefill for all {N_REQUESTS} requests")


def single_device_kernels(clock: CompileClock) -> None:
    m, k, n = KERNEL_SHAPE
    kx, kw = jax.random.split(jax.random.key(SEED))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    with phase("single-device kernels", clock):
        y = ops.dma_stream(x, 1.3)
        e1 = rel_err(y, ref.dma_stream_ref(x, 1.3))
        y = streamed_gather_matmul(x, w)
        e2 = rel_err(y, jnp.dot(x, w, preferred_element_type=jnp.float32)
                     .astype(jnp.bfloat16))
    log(f"dma_stream ({m}x{k}) rel err {e1:.3e}; streamed_gather_matmul "
        f"({m}x{k} @ {k}x{n}) rel err {e2:.3e} (bound "
        f"{KERNEL_REL_BOUND:.3e})")
    check(e1 <= KERNEL_REL_BOUND and e2 <= KERNEL_REL_BOUND,
          "single-device kernel outside its bound")


# ---------------------------------------------------------- four chips ----

def moe_config():
    """dbrx-132b at published widths, depth cut to MOE_LAYERS.  Capacity
    is set so that no token is dropped (DBRX's MoE is dropless): with
    drops, the two dispatch modes would drop different tokens, since
    their per-shard capacities differ."""
    cfg = get_config(MOE_ARCH)
    moe = dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k)
    return dataclasses.replace(cfg, n_layers=MOE_LAYERS, moe=moe)


def collective_counts(hlo_text: str):
    return dict(sorted(collections.Counter(
        d["kind"] for d in collective_op_details(hlo_text)).items()))


def sharded_moe_prefill(mesh, clock: CompileClock) -> None:
    cfg = moe_config()
    param_sh, _, tok_sh = serve_shardings(cfg, mesh, MOE_BATCH, MOE_PROMPT,
                                          SERVE_RULES, jnp.bfloat16)
    with phase("dbrx init", clock):
        params = T.init_params_on_device(jax.random.key(SEED), cfg,
                                         jnp.bfloat16, param_sh)
        jax.block_until_ready(params)
    tokens = jax.random.randint(jax.random.key(SEED + 1),
                                (MOE_BATCH, MOE_PROMPT), 0, cfg.vocab_size)
    logits = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        for mode in ("mem", "mcast"):
            flags = T.RunFlags(compute_dtype=dtype, param_dtype=jnp.bfloat16,
                               remat="none", moe_mode=mode, distributed=True,
                               attn_impl="blockwise")
            name = f"{mode} {jnp.dtype(dtype).name}"
            with phase(f"dbrx prefill {name}", clock):
                step = jax.jit(make_prefill_step(cfg, flags, mesh,
                                                 SERVE_RULES),
                               in_shardings=(param_sh, tok_sh))
                compiled = step.lower(params, tokens).compile()
                out, _ = compiled(params, tokens)
                logits[name] = np.asarray(out, np.float32)
            colls = collective_counts(compiled.as_text())
            log(f"dbrx prefill {name}: collectives {colls}")
            check(logits[name].shape == (MOE_BATCH, 1, cfg.vocab_size),
                  f"logits shape {logits[name].shape}")
            check(bool(np.isfinite(logits[name]).all()),
                  f"non-finite logits ({name})")
            check(("all-to-all" in colls) == (mode == "mcast"),
                  f"all-to-all present only in mcast, not so in {name}")
    for dt in ("bfloat16", "float32"):
        mem, mcast = logits[f"mem {dt}"], logits[f"mcast {dt}"]
        same = int((mem.argmax(-1) == mcast.argmax(-1)).sum())
        log(f"dbrx mem vs mcast, {dt} compute: rel err "
            f"{rel_err(mcast, mem):.3e}, argmax equal in {same} of "
            f"{MOE_BATCH} rows")
    err = rel_err(logits["mcast float32"], logits["mem float32"])
    check(err <= MOE_LOGITS_REL_BOUND,
          f"mem and mcast float32 prefill logits disagree: {err:.3e} > "
          f"{MOE_LOGITS_REL_BOUND:.3e}")


def lax_counterparts(ring: Mesh, axis: str):
    """all_gather + dot, dot + psum_scatter, and a broadcast from rank 0."""
    def smap(f, in_specs, out_specs):
        return jax.jit(jax.shard_map(f, mesh=ring, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def ag_mm(xs, w):
        full = jax.lax.all_gather(xs, axis, axis=0, tiled=True)
        return jnp.dot(full, w, preferred_element_type=jnp.float32
                       ).astype(xs.dtype)

    def mm_rs(xs, ws):
        part = jnp.dot(xs, ws, preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                    tiled=True)

    def bcast(xs):
        return jax.lax.all_gather(xs, axis, axis=0)[0]

    return (smap(ag_mm, (P(axis, None), P()), P()),
            smap(mm_rs, (P(None, axis), P(axis, None)), P(axis, None)),
            smap(bcast, (P(),), P(axis, None)))


def ring_kernels(mesh, clock: CompileClock) -> None:
    # the kernels address their ring neighbour by a 1-tuple mesh id, so
    # they run on the model axis alone, in the device order of ``mesh``
    axis = "model"
    ring = Mesh(mesh.devices.reshape(-1), (axis,),
                axis_types=(AxisType.Auto,))
    n_dev = ring.size
    m, k, n = KERNEL_SHAPE
    keys = jax.random.split(jax.random.key(SEED), 5)
    x_ag = jax.random.normal(keys[0], (n_dev * m, k), jnp.bfloat16)
    w_ag = jax.random.normal(keys[1], (k, n), jnp.bfloat16)
    x_rs = jax.random.normal(keys[2], (m, n_dev * k), jnp.bfloat16)
    w_rs = jax.random.normal(keys[3], (n_dev * k, n), jnp.bfloat16)
    x_mc = jax.random.normal(keys[4], (m, n), jnp.bfloat16)
    lax_ag, lax_rs, lax_mc = lax_counterparts(ring, axis)
    with phase("ring kernels", clock):
        errs = {
            "allgather_matmul": rel_err(
                ops.allgather_matmul(x_ag, w_ag, ring, axis),
                lax_ag(x_ag, w_ag)),
            "reducescatter_matmul": rel_err(
                ops.reducescatter_matmul(x_rs, w_rs, ring, axis),
                lax_rs(x_rs, w_rs)),
            "multicast": rel_err(ops.multicast(x_mc, ring, axis),
                                 lax_mc(x_mc)),
        }
    for name, e in errs.items():
        log(f"{name} (m={m}, k={k}, n={n}, {n_dev} chips) vs lax: "
            f"rel err {e:.3e} (bound {KERNEL_REL_BOUND:.3e})")
        check(e <= KERNEL_REL_BOUND, f"{name} disagrees with lax")


# ---------------------------------------------------------------- main ----

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded MoE prefill and the ring kernels "
                         "on four chips, and nothing else")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"jax {jax.__version__}, {len(devices)} x {dev.device_kind}, "
        f"compile cache {cache_dir}")

    if args.four_chips:
        mesh = make_device_mesh(model=4)
        log(f"mesh {dict(mesh.shape)}: device ids "
            f"{[d.id for d in mesh.devices.flat]}")
        sharded_moe_prefill(mesh, clock)
        ring_kernels(mesh, clock)
    else:
        serve_qwen(clock)
        single_device_kernels(clock)

    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in (devices if args.four_chips else devices[:1])]
    log(f"backend compile {clock.seconds:.3f} s in total, "
        f"{clock.cache_hits} persistent-cache hits; peak device memory "
        f"{[round(p / 2**30, 3) for p in peaks]} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
