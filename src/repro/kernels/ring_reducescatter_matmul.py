"""Matmul fused with ring reduce-scatter (producer-side P2P pipelining).

Computes ``Y = reduce_scatter(X @ W, axis)`` where every rank holds X (m, k_p)
— a column shard of the contraction — and W (k_p, n).  The ring walks the m
dimension in P chunks: at step i each rank multiplies the chunk that is
still (P-1-i) hops from its final owner, adds the partial sum received from
the left, and forwards — matmul and DMA overlap exactly as the paper's
burst-pipelined P2P (the partial-sum packet is the "burst", the add is the
consumer).  After P steps each rank holds its own fully-reduced (m/P, n).

Per-step receive regions and semaphores make the pipeline overrun-safe (a
rank ahead of its right neighbour never clobbers an unconsumed partial).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rs_mm_kernel(axis_name, x_ref, w_ref, y_ref, send_buf, recv_buf,
                  send_sems, recv_sems):
    p = jax.lax.axis_index(axis_name)
    P = jax.lax.axis_size(axis_name)
    right = jax.lax.rem(p + 1, P)
    mloc = y_ref.shape[0]

    def step(i, _):
        # chunk whose owner is (P-1-i) hops to the right of me
        chunk = jax.lax.rem(p + P - 1 - i + P, P)
        part = jnp.dot(x_ref[pl.ds(chunk * mloc, mloc), :], w_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(i > 0)
        def _():
            # partial sum forwarded by the left neighbour for step i
            pltpu.make_async_copy(recv_buf.at[i], recv_buf.at[i],
                                  recv_sems.at[i]).wait()

        total = jax.lax.cond(
            i > 0, lambda: part + recv_buf[i], lambda: part)

        @pl.when(i < P - 1)
        def _():
            send_buf[jax.lax.rem(i, 2)] = total     # stage for sending
            rc = pltpu.make_async_remote_copy(
                src_ref=send_buf.at[jax.lax.rem(i, 2)],
                dst_ref=recv_buf.at[i + 1],
                send_sem=send_sems.at[jax.lax.rem(i, 2)],
                recv_sem=recv_sems.at[i + 1],
                device_id=(right,),
                device_id_type=pltpu.DeviceIdType.MESH)
            rc.start()
            rc.wait_send()

        @pl.when(i == P - 1)
        def _():
            y_ref[...] = total.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, P, step, 0)


def ring_reducescatter_matmul_local(x_local, w_local, *, axis_name: str,
                                    interpret=None):
    """Per-shard body (call inside shard_map).  x_local: (m, k_p), w_local:
    (k_p, n).  Returns (m/P, n): this rank's reduced output shard."""
    P = jax.lax.axis_size(axis_name)
    m, kp = x_local.shape
    n = w_local.shape[1]
    assert m % P == 0
    mloc = m // P
    kernel = functools.partial(_rs_mm_kernel, axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((mloc, n), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, mloc, n), jnp.float32),   # send staging
            pltpu.VMEM((P, mloc, n), jnp.float32),   # per-step recv regions
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((P,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret if interpret is not None else False,
    )(x_local, w_local)
