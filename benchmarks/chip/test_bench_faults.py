"""A whole run of each cell, at a small size on the CPU and past the
harness's look for a chip, comes out ``correct`` as it stands and not
``correct`` with the timed path broken underneath it: a served token
altered where it is produced; for the four-chip prefill also the exchange
between chips left out, half of the batch left out, one row's logits taken
from another row, and tokens dropped (capacity factor 1.0).  A serving
cell on four chips runs its engine over a (1, 4) mesh; on one chip, over
none."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
SEED = 2 ** 32 + 11


def altering(step):
    """``ServeEngine.step`` that alters each request's third served token
    where it is produced."""
    def altered(self):
        info = step(self)
        for req in self._slot_req:
            if req is not None and len(req.generated) == 3:
                req.generated[-1] = (req.generated[-1] + 1) % \
                    self.cfg.vocab_size
        return info
    return altered


def _run_qwen(monkeypatch, fault):
    import run
    from chipbench import harness, testing
    from repro.runtime import engine as E
    monkeypatch.setattr(harness, "setup_jax", lambda *a: "none")
    monkeypatch.setattr(harness, "check_devices",
                        lambda chips: __import__("jax").devices())
    if fault:
        monkeypatch.setattr(E.ServeEngine, "step",
                            altering(E.ServeEngine.step))
    cell = testing.tiny_cell("qwen3-4b.decode-saturated")
    return run.run_cell(cell, SEED, 1.5, False)


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_qwen3_serving(monkeypatch, fault):
    res = _run_qwen(monkeypatch, fault)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0 and "output_tok_s" in res["metrics"]
    assert list(res)[-1] == "checks"


def test_one_chip_engine_gets_no_mesh(monkeypatch):
    import jax
    from chipbench import serving, testing
    from repro.runtime import engine as E
    seen = {}
    init = E.ServeEngine.__init__

    def spy(self, *a, **k):
        seen.update(k)
        init(self, *a, **k)
    monkeypatch.setattr(E.ServeEngine, "__init__", spy)
    eng = serving.build(testing.tiny_cell("qwen3-4b.decode-saturated"), SEED)
    assert eng.mesh is None
    assert seen["mesh"] is None and seen["rules"] is None
    assert seen["mesh_axes"] is None
    assert all(len(leaf.sharding.device_set) == 1
               for leaf in jax.tree.leaves(eng.params))


_QWEN_MESH = r"""
import dataclasses, json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax
import run
from chipbench import harness, serving, testing
from repro.runtime import engine as E
from test_bench_faults import altering
harness.setup_jax = lambda *a: "none"
harness.check_devices = lambda chips: jax.devices()
if {fault!r} == "token_altered":
    E.ServeEngine.step = altering(E.ServeEngine.step)
meshes = []
build = serving.build
def recorded(*a, **k):
    eng = build(*a, **k)
    meshes.append([eng.mesh.shape["data"], eng.mesh.shape["model"]])
    return eng
serving.build = recorded
cell = dataclasses.replace(testing.tiny_cell("qwen3-4b.decode-saturated"),
                           chips=4)
res = run.run_cell(cell, {seed}, 1.5, False)
res["engine_mesh"] = meshes
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_qwen3_serving_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _QWEN_MESH.format(here=HERE, src=SRC, fault=fault, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0 and "output_tok_s" in res["metrics"]
    assert res["device"]["count"] == 4
    assert res["engine_mesh"] == [[1, 4]]


_DBRX = r"""
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax, jax.numpy as jnp
import run
from chipbench import harness, testing
import repro.runtime.serve as RS
harness.setup_jax = lambda *a: "none"
harness.check_devices = lambda chips: jax.devices()
fault = {fault!r}
if fault == "exchange_left_out":
    jax.lax.all_to_all = lambda x, *a, **k: x
    jax.lax.psum = lambda x, *a, **k: x
if fault in ("half_batch", "answer_altered", "one_row"):
    make = RS.make_prefill_step
    def broken(*a, **k):
        step = make(*a, **k)
        def run_step(params, tokens):
            if fault == "half_batch":
                h = tokens.shape[0] // 2
                tokens = tokens.at[h:].set(tokens[:h])
            logits, caches = step(params, tokens)
            if fault == "answer_altered":
                logits = jnp.roll(logits, 1, axis=-1)
            if fault == "one_row":
                logits = logits.at[-1].set(logits[0])
            return logits, caches
        return run_step
    RS.make_prefill_step = broken
cell = testing.tiny_cell("dbrx-132b.prefill-ep4")
if fault == "tokens_dropped":
    cell.conf["run"]["capacity_factor"] = 1.0
res = run.run_cell(cell, {seed}, 1.0, False)
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", [None, "exchange_left_out", "half_batch",
                                   "answer_altered", "one_row",
                                   "tokens_dropped"])
def test_dbrx_prefill_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _DBRX.format(here=HERE, src=SRC, fault=fault, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["checks"]
    assert res["device"]["count"] == 4
