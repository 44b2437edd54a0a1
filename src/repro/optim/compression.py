"""Error-feedback int8 gradient compression for the cross-pod axis.

The multi-pod mesh's "pod" axis crosses the slow inter-pod links; the
all-reduce there is the collective-term bottleneck for data parallelism at
512+ chips.  We compress pod-axis gradients to int8 with per-tensor scales
and keep the quantization residual locally (error feedback), which preserves
convergence (the residual is re-injected next step, making the compressor
unbiased in the long run).

This is a *beyond-paper* distributed-optimization feature; it composes with
the paper's mode system: the pod-axis int8 transfer is a real, priced
transfer — :data:`GRAD_REDUCE_COMPRESSED` below is its typed descriptor,
``compressed_psum`` issues the int32 combine through the socket's reduce
channel with the *on-wire* byte count (one byte per element: 4x fewer
bytes than f32, which is what can flip the planner's MEM<->MCAST verdict
for the pod axis), and the planner emits the matching
``grad_reduce_compressed`` :class:`~repro.core.planner.TransferSpec`
whenever the mesh has a pod axis.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import TransferDescriptor
from repro.core.socket import socket_for_axis

# the pod-axis gradient combine: a fan-in reduction (the socket pins it to
# the memory path — the NoC cannot combine in flight) whose wire payload
# is int8 — word_bytes=1 is the whole point of the compressor
GRAD_REDUCE_COMPRESSED = TransferDescriptor(
    "grad_reduce_compressed", word_bytes=1,
    site="compression.grad_reduce_compressed")


def ef_int8_compress(g: jax.Array, residual: Optional[jax.Array] = None):
    """Returns (q int8, scale f32 scalar, new_residual)."""
    g32 = g.astype(jnp.float32)
    if residual is not None:
        g32 = g32 + residual
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    new_residual = g32 - q.astype(jnp.float32) * scale
    return q, scale, new_residual


def ef_int8_decompress(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(g: jax.Array, axis_name: str,
                    residual: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """int8 all-reduce over ``axis_name`` with error feedback.

    The int8 payloads are summed in int32 (no overflow for pod counts < 2^24)
    and the scales max-reduced; 4x fewer bytes on the slow links than f32.
    The combine is issued through the socket's reduce channel under the
    :data:`GRAD_REDUCE_COMPRESSED` descriptor with ``wire_bytes`` set to
    the int8 payload, so the issue log (and commcheck's descriptor
    universe) prices what actually moves, not the widened accumulator.
    Returns (mean gradient f32, new residual to carry)."""
    g_ef = g.astype(jnp.float32)
    if residual is not None:
        g_ef = g_ef + residual
    local_scale = jnp.maximum(jnp.max(jnp.abs(g_ef)), 1e-30) / 127.0
    # shared scale (pmax) so all pods' int8 payloads are commensurate
    scale = jax.lax.pmax(local_scale, axis_name)
    q = jnp.clip(jnp.round(g_ef / scale), -127, 127).astype(jnp.int8)
    s = socket_for_axis(axis_name).reduce(
        q.astype(jnp.int32), GRAD_REDUCE_COMPRESSED,
        wire_bytes=int(q.size))   # one byte per int8 element on the wire
    n = jax.lax.axis_size(axis_name)
    mean = s.astype(jnp.float32) * scale / n
    new_res = g_ef - q.astype(jnp.float32) * scale
    return mean, new_res
