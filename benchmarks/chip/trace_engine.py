"""Run one serving cell as ``run.py --trace 1`` runs it, with the engine's
own recorder on, and print what the program's spans, counters and named
programs say: the cell's per-layer metrics as ``run.py`` reads them, the
four readings of ``chipbench/programtrace.py``, the checks that tie them to
the harness's own numbers, and (on standard error) the device idle per
step by program span and the window's longest step.  Not part of a
benchmark run: ``run.py`` does not yet hand the engine a recorder.

    python3 benchmarks/chip/trace_engine.py --workload <cell> --seed <n> \
        --seconds <s>
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness  # noqa: E402
from chipbench.harness import log  # noqa: E402


def trace_cell(cell, seed: int, seconds: float) -> dict:
    from chipbench import programtrace as PT
    from chipbench import serving, tracereduce as TR
    from chipbench.peaks import peaks
    from repro.runtime.tracing import EngineTrace

    devices = harness.check_devices(cell.chips)
    harness.setup_jax()
    eng = serving.build(cell, seed)
    eng.trace = EngineTrace()
    trace_dir = tempfile.mkdtemp(prefix="engine-trace-")
    try:
        tracer = harness.Tracer(trace_dir, float(cell.mix["trace_seconds"]))
        run = serving.drive(cell, eng, seed, seconds, time.perf_counter(),
                            tracer)
        path = TR.find_xplane(trace_dir)
        tr = TR.load(path, host_names=("bench.trace", "engine.step.admit",
                                       "engine.step.decode", "traffic.wait",
                                       "prefill.step"))
        tr.devices = tr.devices[:cell.chips]
        window = TR.window_of(tr, "bench.trace")
        reduction = TR.reduce(tr, window)
        pt = PT.load(path)
        pt.trace.devices = pt.trace.devices[:cell.chips]
        program = PT.reduce(pt, window)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    export = eng.trace.export()
    layer = dict(serving.layer_inputs(cell, run), chips=cell.chips,
                 peaks=peaks(devices[0].device_kind), trace=reduction,
                 window_s=seconds)
    metrics = {m["name"]: harness.metric_reader(m["name"])(layer)
               for m in cell.per_layer}
    metrics.update({
        "admit_span_ms.itl": PT.admit_span_ms(export, run.w0, run.w1),
        "decode_device_ms.itl": PT.decode_device_ms(program),
        "host_idle_ms.serve": PT.host_idle_ms(program),
        "batch_occupancy.serve": PT.batch_occupancy(export, run.w0, run.w1)})
    for line in PT.report(program, export, run.w0, run.w1):
        log(line)
    # the profiler's own cost on the host: each span's mean duration in
    # the window, ended while the profiler ran and after it stopped
    profiled = (tracer.t_start, tracer.t_start + tracer.seconds)
    span_ms: dict = {}
    for s in export["spans"]:
        t1 = s.t1_ns * 1e-9
        if run.w0 <= t1 < run.w1:
            part = ("profiled" if profiled[0] <= t1 < profiled[1]
                    else "unprofiled")
            span_ms.setdefault(s.name, {}).setdefault(part, []).append(
                (s.t1_ns - s.t0_ns) * 1e-6)
    plain = [len(s.decode_keys) for s in run.steps if not s.admitted]
    idle = reduction.idle_share * reduction.window_s
    return {
        "device": harness.device_info(devices, cell.chips),
        "metrics": metrics,
        "checks": {
            "decoded_per_plain_step": sum(plain) / len(plain) if plain
            else None,
            "trace_idle_s": idle,
            "idle_under_spans_s": program.attributed_s,
            "idle_outside_spans_s": program.idle_outside_s,
            "spans_dropped": export["dropped"]},
        "idle_per_step_ms": {k: v / program.steps * 1e3 for k, v in
                             program.idle_by_span.items()}
        if program.steps else {},
        "device_clock_shift_ms": program.shift_ms,
        "span_ms": {k: {p: sum(v) / len(v) for p, v in parts.items()}
                    for k, parts in span_ms.items()},
        "programs": {k: {"runs": n, "device_s": t}
                     for k, (n, t) in program.modules.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        result = trace_cell(cell, args.seed, args.seconds)
    except harness.NoAccelerator as e:
        log(f"trace_engine.py: {e}; nothing run")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
