"""Readings that set a cell's limits, on the chip, in one process: the
program's on every seed of ``--seeds`` and the float8 control's on every
seed of ``--control-seeds`` (each after the same short window at the
cell's own load and sizes).  Prints one JSON line per seed; not part of a
benchmark run.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --control-seeds 1,2,3
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness, prefill, serving  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool):
    """The program's run and sample for ``seed``, then the reference's
    readings of it (the control's instead with ``control``)."""
    if cell.mix["driver"] == "open_loop_engine":
        eng = serving.build(cell, seed)
        serving.drive(cell, eng, seed, seconds, time.perf_counter())
        sample = serving.sample_finished(cell, eng, seed)
        del eng
        gc.collect()
        out = serving.check(cell, seed, sample)
        if control:
            out.update({k + ".control": v for k, v in serving.check(
                cell, seed, sample, control=True).items()})
        return out
    pf = prefill.Prefill(cell, seed)
    run = pf.drive(seed, seconds, time.perf_counter())
    sample = pf.sample_for_check(run)
    pf.close()
    del pf
    gc.collect()
    out = prefill.check(cell, seed, sample)
    if control:
        out.update({k + ".control": v for k, v in prefill.check(
            cell, seed, sample, control=True).items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    harness.setup_jax()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = readings(cell, seed, args.seconds, seed in ctrl)
        print(json.dumps(dict(seed=seed, seconds=time.perf_counter() - t0,
                              **out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
