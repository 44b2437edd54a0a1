"""The peak table refuses a device it does not know, and ``run.py`` runs
nothing and prints no result where JAX finds no TPU or too few chips."""

import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.peaks import PEAKS, UnknownDevice, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_known_device_has_sourced_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert all(v["source"] for v in PEAKS.values())


def test_unknown_device_raises():
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", ["qwen3-4b.decode-saturated",
                                  "qwen3-4b.prefill-burst",
                                  "dbrx-132b.prefill-ep4"])
def test_run_exits_nonzero_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_too_few_chips_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU()])
    with pytest.raises(harness.NoAccelerator, match="needs 4 chips"):
        harness.check_devices(4)
    assert harness.check_devices(1)


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_every_cell_finds_its_files():
    for w in harness.load_json(harness.BENCHMARK_JSON)["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.reference() and cell.adapter()
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        assert cell.checks["limits"]
