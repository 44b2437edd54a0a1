"""Record the small profiler trace of the serving engine that
``test_bench_program_trace.py`` reads: a reduced qwen3-4b engine with its
recorder on serves a few requests on one chip, each step inside the
harness's ``engine.step.*`` annotation, all inside ``bench.trace``, with
an idle stretch in ``traffic.wait``.  Writes
``fixtures/trace_engine_1chip.xplane.pb``.

    python3 benchmarks/chip/record_engine_fixture.py
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main() -> int:
    import jax
    import numpy as np

    from repro.configs import get_reduced
    from repro.runtime.engine import ServeEngine
    from repro.runtime.tracing import EngineTrace

    if jax.devices()[0].platform != "tpu":
        print("record_engine_fixture: no TPU", file=sys.stderr)
        return 2
    cfg = get_reduced("qwen3-4b")
    eng = ServeEngine(cfg, prompt_len=16, max_new_tokens=8, n_slots=2,
                      block_size=8, trace=EngineTrace())
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))

    def serve(rids):
        for rid in rids:
            eng.submit(prompts[rid % len(prompts)], 3, rid=rid)
        while eng.pending or eng.n_active:
            admit = bool(eng.pending) and eng.n_active < 2
            with jax.profiler.TraceAnnotation(
                    "engine.step.admit" if admit else "engine.step.decode"):
                eng.step()

    serve([-1])                                   # compiles every program
    tmp = tempfile.mkdtemp(prefix="fixture-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.trace"):
            serve([0, 1])
            with jax.profiler.TraceAnnotation("traffic.wait"):
                time.sleep(0.002)
            serve([2])
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        dst = os.path.join(HERE, "fixtures", "trace_engine_1chip.xplane.pb")
        shutil.copyfile(src, dst)
        print(f"wrote {dst} ({os.path.getsize(dst)} bytes); engine "
              f"recorded {len(eng.trace.export()['steps'])} steps")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
