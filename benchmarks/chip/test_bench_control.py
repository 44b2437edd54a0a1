"""The lower-precision control comes out not correct, at a size a test run
can hold: the plain reference with its linear layers in float8 (e4m3), in
the program's place over the same served sequences, reads past the cell's
limit, while the program itself (bfloat16) reads within it.  The readings
on the chip at the cells' own sizes are in PERF.md."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
SEEDS = (2 ** 31 + 1, 2 ** 31 + 2, 2 ** 31 + 3)
SMALL_QWEN3 = dict(num_hidden_layers=8, hidden_size=256,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                   intermediate_size=512, vocab_size=4096)
# answers long enough that each run checks some hundreds of served tokens
SMALL_OUTPUTS = {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
                 "max": 48}


@pytest.mark.parametrize("seed", SEEDS)
def test_qwen3_control_fails_where_the_program_passes(seed):
    import control
    from chipbench import testing
    cell = testing.tiny_cell("qwen3-4b.decode-saturated",
                             output_tokens=SMALL_OUTPUTS)
    cell = dataclasses.replace(cell, conf=dict(cell.conf, **SMALL_QWEN3),
                               checks=dict(cell.checks, requests=6))
    limit = cell.checks["limits"]["served_gap"]["limit"]
    got = control.readings(cell, seed, 1.5, control=True)
    assert got["served_gap"] <= limit < got["served_gap.control"], got


_DBRX = r"""
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import control
from chipbench import testing
cell = testing.tiny_cell("dbrx-132b.prefill-ep4")
for seed in {seeds!r}:
    print(json.dumps(control.readings(cell, seed, 0.5, control=True)))
"""


def test_dbrx_control_fails_where_the_program_passes():
    from chipbench import testing
    limits = testing.tiny_cell("dbrx-132b.prefill-ep4").checks["limits"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _DBRX.format(here=HERE, src=SRC,
                                            seeds=list(SEEDS))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.strip().splitlines()[-len(SEEDS):]:
        got = json.loads(line)
        assert all(got[k] <= v["limit"] for k, v in limits.items()), got
        assert any(got[k + ".control"] > v["limit"]
                   for k, v in limits.items()), got
