"""The Pallas selective scan (``kernels/selective_scan.py``) against the XLA
chunked scan it replaces on a TPU: run in interpret mode here, on its own,
through ``mamba_apply`` (forward and gradients), under a four-device
``shard_map``, and the rule that picks one path or the other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AbstractMesh

from repro.configs import get_reduced
from repro.core.sharding import use_rules
from repro.kernels import ops
from repro.models import ssm
from repro.runtime.train import SERVE_RULES


def _inputs(seed, B, S, di, N, *, h0, a_log_std=1.0, dt_bias_std=0.0,
            dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 9)
    dt_bias = jax.random.normal(ks[0], (di,)) * dt_bias_std - 2.0
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) + dt_bias)
    return dict(
        dt=dt, B_ssm=jax.random.normal(ks[2], (B, S, N)),
        C_ssm=jax.random.normal(ks[3], (B, S, N)),
        x=jax.random.normal(ks[4], (B, S, di)).astype(dtype),
        z=jax.random.normal(ks[5], (B, S, di)).astype(dtype),
        A_log=jax.random.normal(ks[6], (di, N)) * a_log_std,
        D=1.0 + 0.01 * jax.random.normal(ks[7], (di,)),
        h0=(jax.random.normal(ks[8], (B, di, N)) if h0
            else jnp.zeros((B, di, N))))


# (B, S, di, N, block_t, block_d, h0, A_log std, dt bias std, dtype)
CASES = {
    "nonzero_h0": (2, 256, 128, 16, 128, 128, True, 1.0, 0.0, jnp.float32),
    "ragged_tail": (1, 200, 128, 16, 128, 128, True, 1.0, 0.0, jnp.float32),
    "channel_tiles": (1, 128, 384, 8, 128, 128, True, 1.0, 0.0, jnp.float32),
    # the jamba cell's draw: A_log N(0, 4), dt bias N(0, 1); bf16 x and z
    "cell_decays": (2, 384, 256, 16, 256, 512, False, 2.0, 1.0,
                    jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_chunked_scan(case):
    B, S, di, N, bt, bd, h0, a_std, b_std, dtype = CASES[case]
    a = _inputs(0, B, S, di, N, h0=h0, a_log_std=a_std, dt_bias_std=b_std,
                dtype=dtype)
    y, h_last = ops.selective_scan(
        a["dt"], a["B_ssm"], a["C_ssm"], a["x"], a["z"], a["A_log"], a["D"],
        a["h0"], block_t=bt, block_d=bd, interpret=ops.interpret_params())
    y_ref, h_ref = ssm._chunked_scan(
        {"A_log": a["A_log"], "D": a["D"]}, a["dt"], a["B_ssm"], a["C_ssm"],
        a["x"], a["z"], a["h0"], 8)
    assert y.shape == (B, S, di) and y.dtype == dtype
    assert h_last.shape == (B, di, N)
    np.testing.assert_allclose(h_last, h_ref, rtol=1e-4, atol=1e-4)
    # y in x's dtype: one rounding of the float32 result
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(y_ref.astype(dtype), np.float32),
        rtol=1e-4 if dtype == jnp.float32 else 1e-2, atol=1e-4)


def _mamba(compute_dtype=jnp.float32):
    cfg = get_reduced("jamba2-mini")       # d_inner 128, N 4
    params = ssm.mamba_init(jax.random.key(0), cfg)
    params = dict(params, A_log=params["A_log"] + jax.random.normal(
        jax.random.key(2), params["A_log"].shape))
    x = jax.random.normal(jax.random.key(1), (2, 40, cfg.d_model))
    return cfg, params, x


def test_mamba_apply_gradients_through_the_kernel(monkeypatch):
    """On the kernel path ``mamba_apply``'s forward is the kernel's and its
    gradients are those of the XLA scan: both agree with the XLA path."""
    cfg, params, x = _mamba()

    def loss(p, x):
        y, st = ssm.mamba_apply(p, x, cfg, chunk=8,
                                compute_dtype=jnp.float32)
        return jnp.sum(jnp.sin(y)) + jnp.sum(st["h"] ** 2)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    want_v, want_g = grad(params, x)
    assert ssm._kernel_plan((2, 40, 128)) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm._kernel_plan((2, 40, 128)) is not None
    with pltpu.force_tpu_interpret_mode(ops.interpret_params()):
        jaxpr = jax.make_jaxpr(loss)(params, x)
        got_v, got_g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params, x)
    assert "mamba_selective_scan" in str(jaxpr)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


_SHARDED = """
import jax, jax.numpy as jnp, numpy as np
from jax.experimental.pallas import tpu as pltpu
from repro.configs import get_reduced
from repro.core.sharding import use_rules
from repro.kernels import ops
from repro.launch.mesh import make_device_mesh
from repro.models import ssm
from repro.runtime.train import SERVE_RULES
import dataclasses

cfg = get_reduced("jamba2-mini")
cfg = dataclasses.replace(cfg, d_model=256)      # d_inner 512: 128 a shard
params = ssm.mamba_init(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (2, 72, cfg.d_model))
mesh = make_device_mesh(model=4)

def run(p, x):
    return ssm.mamba_apply(p, x, cfg, chunk=8, compute_dtype=jnp.float32)

# a fresh function for each path: jit's trace cache does not key on the
# backend or the rules
xla = jax.jit(lambda p, x: run(p, x))(params, x)
jax.default_backend = lambda: "tpu"
with pltpu.force_tpu_interpret_mode(ops.interpret_params()):
    whole = jax.jit(lambda p, x: run(p, x))(params, x)
    with use_rules(SERVE_RULES, mesh):
        assert ssm._kernel_plan(x.shape[:2] + (512,))[0] is mesh
        text = str(jax.make_jaxpr(lambda p, x: run(p, x))(params, x))
        sharded = jax.jit(lambda p, x: run(p, x))(params, x)
assert "shard_map" in text and "mamba_selective_scan" in text
for a, b, c in zip(jax.tree.leaves(sharded), jax.tree.leaves(whole),
                   jax.tree.leaves(xla)):
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5)
print("OK")
"""


def test_sharded_kernel_matches_unsharded(subproc):
    """Under a (data 1, model 4) mesh the kernel runs per shard inside a
    ``shard_map``, each chip on its quarter of the channels: the result is
    the unsharded call's, and the XLA scan's."""
    assert "OK" in subproc(_SHARDED, n_devices=4)


@pytest.mark.parametrize("backend,di,seq,kernel", [
    ("tpu", 512, None, True),        # 128 channels a shard
    ("tpu", 1024, None, True),
    ("tpu", 384, None, False),       # 96 channels a shard
    ("tpu", 512, "model", False),    # the sequence split over the chips
    ("cpu", 512, None, False),
])
def test_kernel_plan_picks_the_path(monkeypatch, backend, di, seq, kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = AbstractMesh((1, 4), ("data", "model"))
    with use_rules(dict(SERVE_RULES, seq=seq), mesh):
        plan = ssm._kernel_plan((2, 4096, di))
    assert (plan is not None) == kernel
    if kernel:
        assert plan[0] is mesh


def test_kernel_plan_without_a_mesh(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm._kernel_plan((1, 37, 256)) is not None
    assert ssm._kernel_plan((1, 37, 200)) is None
