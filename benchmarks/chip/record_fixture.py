"""Record the small profiler trace that ``test_bench_trace.py`` reads:
a few steps of a small program on every chip present (on more than one, a
psum across them), inside the harness's host annotations, and an idle
stretch in ``traffic.wait``.  Writes ``fixtures/trace_<n>chip.xplane.pb``.

    python3 benchmarks/chip/record_fixture.py
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 2
    mesh = Mesh(devices, ("x",))

    def body(a, b):
        y = jnp.tanh(a @ b)
        return jax.lax.psum(y, "x")

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                                 out_specs=P("x"), check_vma=False))
    a = jnp.ones((len(devices) * 512, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    step(a, b).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="fixture-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.trace"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("engine.step.decode"):
                    step(a, b).block_until_ready()
            with jax.profiler.TraceAnnotation("traffic.wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("engine.step.admit"):
                step(a, b).block_until_ready()
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        dst = os.path.join(HERE, "fixtures",
                           f"trace_{len(devices)}chip.xplane.pb")
        shutil.copyfile(src, dst)
        print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
