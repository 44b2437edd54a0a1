"""Accelerator synchronization via a dedicated small-payload path (paper C3).

ESP's proposal: reserve a slice of the accelerator's dataset for
*synchronization messages* carried by the fully-coherent path (MESI via the
3 coherence NoC planes) while bulk transfers stay on the DMA planes.  TPUs
have no inter-chip cache coherence; the transferable insight is the *split*:
tiny control values ride latency-optimized collectives, decoupled from and
explicitly ordered against the bulk stream.

``flag_allreduce``/``barrier`` are the control path;
``ordered_after``/``fence`` provide the ordering (XLA's optimization_barrier
is the analogue of the coherence protocol's ordering guarantees).  Inside
Pallas kernels the same role is played by DMA semaphores
(`kernels/dma_isa.py`).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp



def flag_allreduce(flag: jax.Array, axis_name: str) -> jax.Array:
    """Exchange a tiny control flag across ``axis_name`` (sync region)."""
    assert flag.size <= 128, "sync region is for small control payloads"
    return jax.lax.psum(flag, axis_name)


def barrier(axis_name: str) -> jax.Array:
    """All ranks reach this point; returns the participant count."""
    return jax.lax.psum(jnp.ones((), jnp.int32), axis_name)


def ordered_after(bulk, flag):
    """Order a bulk value after a control flag (consume-side sync): the
    returned bulk tensor cannot be scheduled before ``flag`` is available."""
    flag = jnp.sum(flag).astype(bulk.dtype if jnp.issubdtype(
        bulk.dtype, jnp.floating) else jnp.float32)
    bulk2, _ = jax.lax.optimization_barrier((bulk, flag))
    return bulk2


def fence(*values):
    """Mutual ordering fence across a group of values."""
    return jax.lax.optimization_barrier(values)


def ready_check(step_ok: jax.Array, axis_name: str) -> jax.Array:
    """Global 'every producer has produced' check before consumers proceed —
    the pull-request aggregation a multicast producer performs (it waits for
    N consumer requests before sending)."""
    n = jax.lax.axis_size(axis_name)
    got = flag_allreduce(step_ok.astype(jnp.int32), axis_name)
    return got == n
