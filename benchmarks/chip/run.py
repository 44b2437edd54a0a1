"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, limits and per-layer metrics are found by name
under ``benchmarks/chip/`` (see ``chipbench/harness.py``).  Weights and
inputs come from ``--seed``.  After set-up (weights, compiles from the
persistent cache in ``benchmarks/chip/.jax_cache``, warm-up of every shape
the window uses) the window lasts ``--seconds``; then what the timed path
produced is compared with the plain float32 reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the harness's own clocks and
from a profiler trace of the start of the window.  The last line of
standard output is one JSON object; the numbers compared, each with its
limit, are the last lines of standard error and the result's last key.
Exits nonzero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness  # noqa: E402
from chipbench.harness import log  # noqa: E402


def run_cell(cell, seed: int, seconds: float, trace: bool) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    from chipbench import prefill, serving, tracereduce
    from chipbench.peaks import peaks

    devices = harness.check_devices(cell.chips)
    kind = devices[0].device_kind
    peak = peaks(kind) if trace else None     # only per-layer metrics read it
    cache = harness.setup_jax()
    clock = harness.CompileClock()
    log(f"{cell.name}: jax {jax.__version__}, {len(devices)} x {kind}, "
        f"compile cache {cache}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = harness.Tracer(trace_dir, float(cell.mix["trace_seconds"])) \
        if trace else None
    driver = cell.mix["driver"]
    try:
        if driver == "open_loop_engine":
            eng = serving.build(cell, seed)
            t_start = time.perf_counter()
            c0 = clock.compiles
            setup_s = t_start + float(cell.mix["warmin_s"]) - T_PROCESS
            run = serving.drive(cell, eng, seed, seconds, t_start, tracer)
            e2e = serving.metrics_e2e(run, seconds)
            inputs = serving.layer_inputs(cell, run)
            attempted, failed = run.attempted, run.failed
        elif driver == "closed_loop_prefill":
            pf = prefill.Prefill(cell, seed)
            t_start = time.perf_counter()
            c0 = clock.compiles
            setup_s = t_start - T_PROCESS
            run = pf.drive(seed, seconds, t_start, tracer)
            e2e = prefill.metrics_e2e(run)
            inputs = prefill.layer_inputs(cell, run)
            attempted, failed = len(run.steps) * pf.B, 0
        else:
            raise ValueError(f"unknown driver {driver!r}")
        in_window = clock.compiles - c0
        log(f"set-up {setup_s:.3f} s, of which backend compile "
            f"{clock.seconds:.3f} s ({clock.cache_hits} cache hits); "
            f"{in_window} compiles during the window")
        device = harness.device_info(devices, cell.chips)

        reduction = None
        if trace:
            tr = tracereduce.load(
                tracereduce.find_xplane(trace_dir),
                host_names=("bench.trace", "engine.step.admit",
                            "engine.step.decode", "traffic.wait",
                            "prefill.step"))
            tr.devices = tr.devices[:cell.chips]
            reduction = tracereduce.reduce(
                tr, tracereduce.window_of(tr, "bench.trace"))
            device.update(busy_s=reduction.busy_s,
                          window_s=reduction.window_s)

        # the program's state goes before the reference runs
        if driver == "open_loop_engine":
            sample = serving.sample_finished(cell, eng, seed)
            del eng
            gc.collect()
            readings = serving.check(cell, seed, sample)
            log(f"check: {sample.get('n', 0)} requests, "
                f"{sample.get('served', 0)} served tokens")
        else:
            sample = pf.sample_for_check(run)
            pf.close()
            del pf
            readings = prefill.check(cell, seed, sample)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    correct, checks = harness.judge(cell.checks, readings)
    layer = dict(inputs, chips=cell.chips, peaks=peak, trace=reduction,
                 window_s=seconds)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"])(layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.top_ops,
                               "idle_gaps": reduction.idle_by_host}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except harness.NoAccelerator as e:
        log(f"run.py: {e}; nothing run")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result, allow_nan=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
