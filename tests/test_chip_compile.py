"""Compile what ``chip_smoke.py`` runs, for a described TPU v5e (2x2), at
its real sizes: the chip's compiler refuses here, at no chip time, what
interpret mode cannot see (VMEM overflow, unaligned slices, a program
that does not fit the device's memory, kernel parameters the chip's
compiler rejects).  Nothing runs; nothing is placed on a device.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.streamed_gather import streamed_gather_matmul
from repro.launch.mesh import make_device_mesh
from repro.models import transformer as T
from repro.runtime.serve import make_prefill_step, serve_shardings
from repro.runtime.train import SERVE_RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16e9        # one TPU v5e chip


@pytest.fixture(scope="module")
def smoke():
    """``chip_smoke.py`` as a module, for its shapes and configs."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return make_device_mesh(model=4, devices=topo.devices)


def _peak(compiled) -> int:
    return compiled.memory_analysis().peak_memory_in_bytes


def _with_sharding(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _serve_params(smoke, one_chip):
    cfg = get_config(smoke.SERVE_ARCH)
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.key(0), cfg, jnp.bfloat16))
    return cfg, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)


def test_serve_init_fits_one_chip(smoke, one_chip):
    """The jitted init draws, scales and casts each leaf in one program:
    its peak is the bf16 weights, with no float32 transient beside them."""
    cfg, params = _serve_params(smoke, one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    init = jax.jit(functools.partial(T.init_params, cfg=cfg,
                                     dtype=jnp.bfloat16),
                   out_shardings=one_chip)
    compiled = init.lower(
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    ).compile()
    weights = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    ma = compiled.memory_analysis()
    # the device layout pads the small (norm) leaves a little
    assert weights <= ma.output_size_in_bytes < 1.001 * weights
    assert ma.temp_size_in_bytes < 0.05 * weights
    assert _peak(compiled) < HBM_BYTES


def test_serve_prefill_fits_one_chip(smoke, one_chip):
    cfg, params = _serve_params(smoke, one_chip)
    flags = T.RunFlags(param_dtype=jnp.bfloat16, remat="none")
    tokens = jax.ShapeDtypeStruct((1, smoke.PROMPT_LEN), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(make_prefill_step(cfg, flags)).lower(
        params, tokens).compile()
    assert _peak(compiled) < HBM_BYTES


@pytest.mark.parametrize("compute_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("mode", ["mem", "mcast"])
def test_moe_prefill_on_four_chips(smoke, four_chips, mode, compute_dtype):
    cfg = smoke.moe_config()
    param_sh, _, tok_sh = serve_shardings(
        cfg, four_chips, smoke.MOE_BATCH, smoke.MOE_PROMPT, SERVE_RULES,
        jnp.bfloat16)
    params = _with_sharding(jax.eval_shape(
        lambda: T.init_params(jax.random.key(0), cfg, jnp.bfloat16)),
        param_sh)
    tokens = jax.ShapeDtypeStruct((smoke.MOE_BATCH, smoke.MOE_PROMPT),
                                  jnp.int32, sharding=tok_sh)
    flags = T.RunFlags(compute_dtype=compute_dtype,
                       param_dtype=jnp.bfloat16, remat="none",
                       moe_mode=mode, distributed=True,
                       attn_impl="blockwise")
    compiled = jax.jit(make_prefill_step(cfg, flags, four_chips,
                                         SERVE_RULES),
                       in_shardings=(param_sh, tok_sh)).lower(
        params, tokens).compile()
    assert _peak(compiled) < HBM_BYTES
    has_a2a = "all-to-all" in smoke.collective_counts(compiled.as_text())
    assert has_a2a == (mode == "mcast")


def _kernel_programs(smoke, one_chip, four_chips):
    m, k, n = smoke.KERNEL_SHAPE
    bf = jnp.bfloat16
    ring = Mesh(four_chips.devices.reshape(-1), ("model",),
                axis_types=(AxisType.Auto,))
    n_dev = ring.size

    def on_ring(shape, spec):
        return jax.ShapeDtypeStruct(shape, bf,
                                    sharding=NamedSharding(ring, spec))

    def single(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return {
        "allgather_matmul": (
            lambda x, w: ops.allgather_matmul(x, w, ring, "model"),
            on_ring((n_dev * m, k), P("model", None)), on_ring((k, n), P())),
        "reducescatter_matmul": (
            lambda x, w: ops.reducescatter_matmul(x, w, ring, "model"),
            on_ring((m, n_dev * k), P(None, "model")),
            on_ring((n_dev * k, n), P("model", None))),
        "multicast": (
            lambda x: ops.multicast(x, ring, "model"),
            on_ring((m, n), P())),
        "streamed_gather_matmul": (
            streamed_gather_matmul, single((m, k)), single((k, n))),
        "dma_double_buffer_stream": (
            lambda x: ops.dma_stream(x, 1.3), single((m, k))),
    }


@pytest.mark.parametrize("name", [
    "allgather_matmul", "reducescatter_matmul", "multicast",
    "streamed_gather_matmul", "dma_double_buffer_stream"])
def test_kernel_compiles_for_the_chip(smoke, one_chip, four_chips, name):
    fn, *args = _kernel_programs(smoke, one_chip, four_chips)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
