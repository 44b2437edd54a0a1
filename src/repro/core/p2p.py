"""Pull-based point-to-point transfers (paper C1), jax-native.

ESP's P2P is *pull-based*: the consumer sends a request and the producer
forwards data only once the request arrives, satisfying the consumption
assumption (messages on the NoC are always drained -> no message-dependent
deadlock).  On a TPU pod the analogue is ``ppermute``: the collective is
issued by *both* endpoints (the receive buffer is committed before data
moves), which gives exactly the same guarantee — a ppermute cannot leave
undrained traffic in the ICI fabric.  Inside Pallas kernels the same
contract appears as the receiver-side DMA semaphore
(`kernels/ring_allgather_matmul`).

These helpers are used by pipeline-parallel stage forwarding and the
serving pipeline example.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import validate_p2p_totals, reblock


def p2p_shift(x: jax.Array, axis_name: str, offset: int = 1) -> jax.Array:
    """Forward ``x`` from stage i to stage i+offset (ring) along
    ``axis_name``.  Must be called inside shard_map/pmap collective context."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def p2p_send_recv(x: jax.Array, axis_name: str, src: int, dst: int) -> jax.Array:
    """Single producer -> single consumer transfer along ``axis_name``.
    Ranks other than ``dst`` receive zeros (nothing is addressed to them)."""
    return jax.lax.ppermute(x, axis_name, [(src, dst)])


def p2p_send_recv_dynamic(x: jax.Array, axis_name: str, src, dst) -> jax.Array:
    """P2P transfer whose peer indices may be *traced* values (the socket's
    LUT virtualization: the registry hands ranks in as step arguments, so
    retargeting a peer is a new argument value, not a retrace).

    ``ppermute`` requires a static permutation, so the dynamic path rides
    the sync-capable collective instead: the producer's value is masked in,
    carried by a psum (every rank issues it — consumption assumption
    holds), and masked out everywhere but ``dst``.  Wire cost is a
    broadcast, the price of dynamic peer selection."""
    idx = jax.lax.axis_index(axis_name)
    contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
    y = jax.lax.psum(contrib, axis_name)
    return jnp.where(idx == dst, y, jnp.zeros_like(y))


def p2p_reblocked(x: jax.Array, axis_name: str, src: int, dst: int,
                  producer_burst: int, consumer_burst: int) -> jax.Array:
    """Flexible P2P (C1): producer emits bursts of ``producer_burst`` words;
    consumer ingests bursts of ``consumer_burst`` words.  Only the totals
    must agree — checked before the transfer."""
    total = x.size
    n_p, n_c = total // producer_burst, total // consumer_burst
    validate_p2p_totals([producer_burst] * n_p, [consumer_burst] * n_c)
    y = p2p_send_recv(x, axis_name, src, dst)
    return reblock(y, consumer_burst)


def pipeline_stage_forward(x: jax.Array, axis_name: str) -> jax.Array:
    """GPipe-style stage hand-off: every stage forwards its activation to the
    next (the paper's NN example: 'a previous layer's outputs from another
    accelerator')."""
    return p2p_shift(x, axis_name, offset=1)
