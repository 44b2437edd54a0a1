"""Find an open-loop serving cell's knee once: offer its mix at a series of
rates, one after the other in one process, and print for each what came
out.  The knee is the highest rate without a growing backlog; the cell's
traffic file then carries a rate fixed from it.  Not part of a benchmark
run.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 1.0,1.5,2.0
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness, serving  # noqa: E402
from chipbench.harness import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    harness.setup_jax()
    eng = serving.build(cell, args.seed)
    cell.mix["drain_s"] = 0
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        run = serving.drive(cell, eng, args.seed, args.seconds, t0,
                            rate=rate)
        queued = len(eng.pending)
        done = [r for r in eng.completed if run.w0 <= r.t_done < run.w1]
        finite = [t for t in run.ttft_s if t != float("inf")]
        row = {"rate_per_s": rate,
               "completed_per_s": len(done) / args.seconds,
               "queued_at_close": queued,
               "unstarted_in_window": len(run.ttft_s) - len(finite),
               "output_tok_s": run.tokens_in_window / args.seconds,
               "ttft_p50_ms": percentile(finite, 50) * 1e3,
               "ttft_p95_ms": percentile(run.ttft_s, 95) * 1e3,
               "itl_p95_ms": percentile(run.itl_s, 95) * 1e3}
        print(json.dumps(row), flush=True)
        while eng.pending or eng.n_active:      # empty it for the next rate
            eng.pending.clear()
            eng.step()
        eng.completed.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
