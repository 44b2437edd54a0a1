"""End-to-end behaviour: train -> checkpoint -> restart -> identical
continuation; serve pipeline; dry-run plumbing on a small mesh (subprocess)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.data import SyntheticTokenStream
from repro.models.transformer import RunFlags
from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.runtime.train import make_train_step, init_state


def test_checkpoint_restart_bitwise_continuation(tmp_path):
    """Train 6 steps straight vs. 3 steps -> checkpoint -> restore -> 3
    steps: identical final loss (determinism end to end)."""
    cfg = get_reduced("smollm-135m")
    flags = RunFlags(remat="none")
    step_fn, _, _ = make_train_step(cfg, flags)
    jstep = jax.jit(step_fn)
    stream = SyntheticTokenStream(cfg.vocab_size, 4, 64)
    batches = [
        {k: jnp.asarray(v) for k, v in stream.batch(s).items()}
        for s in range(6)]

    state = init_state(jax.random.key(0), cfg, flags)
    for b in batches:
        state, metrics = jstep(state, b)
    loss_straight = float(metrics["loss"])

    state2 = init_state(jax.random.key(0), cfg, flags)
    for b in batches[:3]:
        state2, _ = jstep(state2, b)
    save_checkpoint(str(tmp_path), 3, state2)
    assert latest_step(str(tmp_path)) == 3

    state3 = restore_checkpoint(str(tmp_path), 3, state2)
    for b in batches[3:]:
        state3, metrics3 = jstep(state3, b)
    assert float(metrics3["loss"]) == pytest.approx(loss_straight, rel=1e-5)


def test_moe_arch_trains(tmp_path):
    cfg = get_reduced("dbrx-132b")
    flags = RunFlags(remat="none")
    step_fn, _, _ = make_train_step(cfg, flags, lr=1e-3)
    jstep = jax.jit(step_fn, donate_argnums=0)
    state = init_state(jax.random.key(0), cfg, flags)
    stream = SyntheticTokenStream(cfg.vocab_size, 4, 32)
    losses = []
    for s in range(8):
        batch = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}
        state, metrics = jstep(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


_DRYRUN_SMALL = r"""
import jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import lower_cell, make_flags
from repro.launch import hlo_analysis

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# one family of each kind x (train, decode)
for arch in ("smollm-135m", "dbrx-132b", "falcon-mamba-7b",
             "recurrentgemma-9b"):
    cfg = get_reduced(arch)
    for shape in (ShapeConfig("t", 128, 16, "train"),
                  ShapeConfig("d", 128, 16, "decode")):
        flags = make_flags(cfg, shape)
        lowered, _ = lower_cell(cfg, shape, mesh, flags)
        compiled = lowered.compile()
        roof = hlo_analysis.analyze(compiled, model_flops_total=1e9,
                                    n_chips=16)
        assert roof.flops_per_dev > 0
        assert roof.bound_time() > 0
        print(f"{arch} {shape.kind} OK "
              f"dom={roof.dominant}", flush=True)
print("DRYRUN_SMALL_OK", flush=True)
"""


def test_dryrun_plumbing_small_mesh(subproc):
    out = subproc(_DRYRUN_SMALL, n_devices=16)
    assert "DRYRUN_SMALL_OK" in out
    assert out.count("OK") >= 9
