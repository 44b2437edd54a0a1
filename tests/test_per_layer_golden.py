"""Per-layer transfer-spec golden test.

A 4-layer MoE transformer's compiled prefill step must emit ONE spec per
layer per collective archetype with stable ``.L<i>`` names — the scanned
stack's trip count is the layer count, same-kind ops within one layer
aggregate, and the unscanned epilogue collectives (embedding/final-norm
gathers, last-position permute) land as one trailing pseudo-layer each.
The full (name, fan_out, layer) list is pinned against the checked-in
``golden_per_layer_specs.json``: any change to the HLO -> TransferSpec
mapping that renames, reorders, or re-counts per-layer transfers must
update the golden deliberately.
"""

import json
import os

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden_per_layer_specs.json")

_CODE = r"""
import dataclasses, json
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import build_comm_plan, lower_cell, make_flags
from repro.launch.hlo_analysis import transfer_specs_from_hlo

cfg = dataclasses.replace(get_reduced("dbrx-132b"), name="dbrx-4l",
                          n_layers=4)
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
shape = ShapeConfig("g", 128, 16, "prefill")
flags = make_flags(cfg, shape, moe_mode="mcast")
plan, _ = build_comm_plan("auto", cfg, shape, mesh)
lowered, _ = lower_cell(cfg, shape, mesh, flags, comm_plan=plan)
specs = transfer_specs_from_hlo(lowered.compile().as_text())
print("SPECS_JSON=" + json.dumps(
    [[s.name, s.fan_out, s.layer] for s in specs]))
"""


def test_per_layer_specs_golden(subproc):
    out = subproc(_CODE, n_devices=16)
    got = json.loads(out.split("SPECS_JSON=", 1)[1].splitlines()[0])
    with open(GOLDEN) as f:
        want = json.load(f)
    assert got == want, (
        "per-layer transfer specs diverged from the golden list — if the "
        "HLO mapping changed deliberately, regenerate "
        "tests/golden_per_layer_specs.json")
    # structural invariant behind the golden: the 4 scanned layers appear
    # as .L0-.L3 for every archetype the step exhibits
    names = {n for n, _, _ in got}
    for arch in ("weights", "moe_dispatch", "stage_activation",
                 "grad_reduce"):
        assert {f"{arch}.L{i}" for i in range(4)} <= names, arch
