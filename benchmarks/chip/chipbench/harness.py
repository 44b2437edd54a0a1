"""What every cell shares: finding its files by name, the compile cache and
compile clock, the device check, the result line.

Layout under ``benchmarks/chip/`` (each item found by the name that
``BENCHMARK.json`` gives it):

* ``configs/<config>.json``: a model configuration as it is run; its
  ``reference`` names ``references/<family>.py`` (the plain float32
  forward) and ``adapters/<family>.py`` (the program's view of it);
* ``traffic/<mix>.json``: a mix's parameters; ``driver`` names the loop
  that drives it (``open_loop_engine`` or ``closed_loop_prefill``);
* ``metrics/<metric>.py``: one per-layer metric, ``read(run)``;
* ``checks/<cell>.json``: the limits of the comparison that decides
  ``correct`` in that cell.

``pending.json`` holds, in ``BENCHMARK.json``'s shape, cells that are
built and tested on the CPU but not yet measured on the chip.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
PENDING_JSON = os.path.join(HERE, "pending.json")
CACHE_DIR = os.path.join(HERE, ".jax_cache")


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    checks: Dict[str, Any]

    @property
    def family(self) -> str:
        return self.conf["reference"]

    def reference(self):
        return importlib.import_module(f"references.{self.family}")

    def adapter(self):
        return importlib.import_module(f"adapters.{self.family}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: str = BENCHMARK_JSON) -> Cell:
    bench = load_json(bench_path)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_json(os.path.join(ROOT, cfg["file"]))
    conf.setdefault("name", w["config"])
    mix = load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    checks = load_json(os.path.join(HERE, "checks", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), conf=conf, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                checks=checks)


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax(cache_dir: Optional[str] = None) -> str:
    """The persistent compilation cache, every program in it (no minimum
    compile time), at a fixed path inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir \
        or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


class Tracer:
    """A profiler session over the first ``seconds`` of the window, inside
    a ``bench.trace`` host span; the drivers poll it between steps."""

    def __init__(self, trace_dir: Optional[str], seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self.state = "off" if trace_dir else "never"
        self.ann = None

    def maybe_start(self, now: float, w0: float):
        import jax
        if self.state == "off" and now >= w0:
            jax.profiler.start_trace(self.dir)
            self.ann = jax.profiler.TraceAnnotation("bench.trace")
            self.ann.__enter__()
            self.t_start, self.state = time.perf_counter(), "on"
            log(f"profiler started in {self.t_start - now:.3f} s")

    def maybe_stop(self, now: float, force: bool = False):
        import jax
        if self.state == "on" and (force or now - self.t_start >=
                                   self.seconds):
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), how many, and the persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between closest ranks;
    ``inf`` values count as the largest."""
    vals = sorted(values)
    if not vals:
        return math.nan
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if vals[hi] == math.inf:
        return math.inf
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(devices, chips: int) -> Dict[str, Any]:
    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def judge(checks_spec: Dict[str, Any], readings: Dict[str, float]):
    """``correct`` and the compared numbers beside their limits.  A number
    that is missing or not finite fails."""
    out, ok = {}, True
    for name, spec in checks_spec["limits"].items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= spec["limit"]
        ok = ok and good
        out[name] = {"value": v, "limit": spec["limit"]}
    return ok, out
