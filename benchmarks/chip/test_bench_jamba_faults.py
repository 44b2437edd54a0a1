"""The whole Jamba cell at a small size through ``run.run_cell`` on four
host devices, past the harness's look for a chip: ``correct`` as it
stands, and not ``correct`` with the program broken underneath it: the
Mamba mixers' inner norms on dt, B and C left out, the top-2 gates divided
by their sum, rotary positions applied, the exchange between chips left
out.  (The conv tail dropped between prefill and decode is
``test_bench_jamba.py``'s engine case.)"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
SEED = 2 ** 32 + 21

FAULTS = ["inner_norms_left_out", "gates_renormalised", "rope_applied",
          "exchange_left_out"]

_RUN = r"""
import dataclasses, json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax
import run
from chipbench import harness
from test_bench_jamba import tiny_cell
from adapters import jamba as AJ
from repro.models import moe as M, ssm as SSM
setup = harness.setup_jax
harness.setup_jax = lambda *a: setup({cache!r})
harness.check_devices = lambda chips: jax.devices()
patches = {{
    "inner_norms_left_out": (SSM, "rmsnorm", lambda p, x: x),
    "gates_renormalised": (M, "_route", lambda r, x, k, normalize=True,
                           _route=M._route: _route(r, x, k, True)),
    "rope_applied": (AJ, "arch_config", lambda conf, _a=AJ.arch_config:
                     dataclasses.replace(_a(conf), rope=True)),
    "exchange_left_out": (jax.lax, "all_to_all", lambda x, *a, **k: x),
}}
for fault in [None] + {faults!r}:
    undo = []
    if fault is not None:
        mod, name, fn = patches[fault]
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, fn)
        if fault == "exchange_left_out":
            undo.append((jax.lax, "psum", jax.lax.psum))
            jax.lax.psum = lambda x, *a, **k: x
    res = run.run_cell(tiny_cell(), {seed}, 1.0, False)
    for mod, name, fn in undo:
        setattr(mod, name, fn)
    print(json.dumps(dict(res, fault=fault)), flush=True)
"""


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    """The whole tiny cell through ``run.run_cell`` on four host devices,
    sound and under each fault, in one process (the runs share what they
    compile alike through a compile cache of their own)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _RUN.format(here=HERE, src=SRC, faults=FAULTS, seed=SEED,
                       cache=str(tmp_path_factory.mktemp("jax_cache")))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=HERE,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(s) for s in proc.stdout.splitlines()
             if s.startswith("{")]
    return {r["fault"]: r for r in lines}


@pytest.mark.parametrize("fault", [None] + FAULTS)
def test_sharded_prefill_on_four_devices(four_device_runs, fault):
    res = four_device_runs[fault]
    assert res["correct"] is (fault is None), res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and "prefill_tok_s" in res["metrics"]
