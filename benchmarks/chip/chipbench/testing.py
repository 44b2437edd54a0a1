"""Small copies of the benchmark's cells, for the tests on the CPU: the
same files and code paths, at sizes a test run can hold."""

from __future__ import annotations

import copy
import dataclasses

from chipbench import harness

TINY_QWEN3 = dict(num_hidden_layers=2, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  intermediate_size=128, vocab_size=256)


def tiny_cell(name: str, **mix_overrides) -> harness.Cell:
    """``name``'s cell (from ``BENCHMARK.json`` or ``pending.json``) with a
    small model and a short mix."""
    bench = harness.load_json(harness.BENCHMARK_JSON)
    in_bench = any(w["name"] == name for w in bench["workloads"])
    cell = harness.load_cell(name, harness.BENCHMARK_JSON if in_bench
                             else harness.PENDING_JSON)
    conf = copy.deepcopy(cell.conf)
    mix = dict(cell.mix)
    if cell.family == "qwen3":
        conf.update(TINY_QWEN3)
        mix.update(prompt_tokens=16, slots=2, block_size=8, warmin_s=0.2,
                   drain_s=5, trace_seconds=0.3,
                   output_tokens={"dist": "lognormal", "median": 6,
                                  "sigma": 0.5, "min": 2, "max": 16},
                   arrivals=dict(mix["arrivals"], rate_per_s=20.0))
    else:
        # the published 16 experts and top-4, so routing flips weigh what
        # they weigh in the cell
        conf.update(d_model=128, n_heads=4, n_layers=2, vocab_size=256)
        conf["attn_config"] = dict(conf["attn_config"], kv_n_heads=2)
        conf["ffn_config"] = dict(conf["ffn_config"], ffn_hidden_size=128)
        mix.update(batch=2, prompt_tokens=32, batches=4, trace_seconds=0.3)
    mix.update(mix_overrides)
    checks = dict(cell.checks)
    if "requests" in checks:
        checks["requests"] = 3
    else:
        checks.update(steps=2, steps_from=2)
    return dataclasses.replace(cell, conf=conf, mix=mix, checks=checks)
