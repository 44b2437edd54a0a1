"""Jit'd public wrappers: shard_map plumbing + interpret-mode selection.

On CPU (tests) pass ``interpret=interpret_params()``; on TPU leave the
default (compiled).  The collective wrappers build the shard_map over the
given mesh axis so callers hand in global arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.ring_allgather_matmul import ring_allgather_matmul_local
from repro.kernels.ring_reducescatter_matmul import ring_reducescatter_matmul_local
from repro.kernels.multicast_stream import multicast_stream_local
from repro.kernels.dma_double_buffer import dma_double_buffer_stream
# decode attention over paged KV, read in place through the block table
from repro.kernels.paged_attention import paged_decode_attention  # noqa: F401
# the Mamba-1 selective scan with the state held in VMEM
from repro.kernels.selective_scan import selective_scan  # noqa: F401


def interpret_params():
    """Interpret-mode selector for CPU test runs: the TPU interpreter with
    per-device VMEM, DMA, remote copies and semaphores, executing each DMA
    at its wait (robust when one process runs many kernels)."""
    return pltpu.InterpretParams(dma_execution_mode="on_wait")


def allgather_matmul(x, w, mesh, axis_name="x", *, interpret=None):
    """x: (M, k) row-sharded over ``axis_name``; w: (k, n) replicated.
    Returns (M, n) = x @ w, gathered on every rank."""
    fn = functools.partial(ring_allgather_matmul_local, axis_name=axis_name,
                           interpret=interpret)
    return jax.jit(jax.shard_map(
        lambda xs, ws: fn(xs, ws), mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)


def reducescatter_matmul(x, w, mesh, axis_name="x", *, interpret=None):
    """x: (m, K) column-sharded on K; w: (K, n) row-sharded on K.
    Returns (m, n) = x @ w with rows scattered over ranks."""
    fn = functools.partial(ring_reducescatter_matmul_local,
                           axis_name=axis_name, interpret=interpret)
    return jax.jit(jax.shard_map(
        lambda xs, ws: fn(xs, ws), mesh=mesh,
        in_specs=(P(None, axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None), check_vma=False))(x, w)


def multicast(x, mesh, axis_name="x", src=0, n_chunks=4, *, interpret=None):
    """x: (m, n) source payload (replicated input; only rank ``src``'s value
    matters).  Returns (P*m, n): every rank's received copy, stacked."""
    fn = functools.partial(multicast_stream_local, axis_name=axis_name,
                           src=src, n_chunks=n_chunks, interpret=interpret)
    return jax.jit(jax.shard_map(
        lambda xs: fn(xs), mesh=mesh,
        in_specs=(P(None, None),),
        out_specs=P(axis_name, None), check_vma=False))(x)


def dma_stream(x, scale, n_blocks=4, *, interpret=None):
    """Single-device streaming op: y = silu(x * scale)."""
    return jax.jit(functools.partial(
        dma_double_buffer_stream, n_blocks=n_blocks, interpret=interpret))(
        x, jnp.asarray([scale], jnp.float32))

