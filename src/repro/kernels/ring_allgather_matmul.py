"""Ring all-gather fused with matmul (P2P burst pipelining on the MXU).

Computes ``Y = X @ W`` where X is row-sharded over a ring axis: every step
multiplies the chunk already in VMEM while the same chunk streams onward to
the right neighbour via an async remote DMA — the paper's Fig. 6 mechanism
(consumer starts on burst k while burst k+1 is in flight) applied to the
tensor-parallel all-gather.  The pull-based handshake is the receive
semaphore: a chunk is consumed (dot-producted / forwarded) only after its
recv semaphore fires (consumption assumption, C1).

Race-freedom by construction: every chunk owns a distinct gather-buffer
region (written exactly once) and a distinct per-step semaphore — no slot
reuse, so a fast sender can run ahead without overrunning a slow receiver
(the deadlock-freedom argument the paper inherits from [18]).

VMEM budget: P*m*k (gather buffer) + k*n (W) + P*m*n (Y); callers pick
chunk sizes so this fits ~16 MB VMEM with 128-aligned matmul dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ag_mm_kernel(axis_name, x_hbm, w_ref, y_ref, gbuf, send_sems, recv_sems,
                  local_sem):
    p = jax.lax.axis_index(axis_name)
    P = jax.lax.axis_size(axis_name)
    right = jax.lax.rem(p + 1, P)
    m = x_hbm.shape[0]

    # stage my shard into my gather slot (IDMA/CDMA pair)
    local = pltpu.make_async_copy(x_hbm, gbuf.at[p], local_sem)
    local.start()
    local.wait()

    def step(i, _):
        cur = jax.lax.rem(p - i + P, P)      # chunk consumed this step

        @pl.when(i > 0)
        def _():
            # pull-side handshake: chunk `cur` arrived from the left
            pltpu.make_async_copy(gbuf.at[cur], gbuf.at[cur],
                                  recv_sems.at[i - 1]).wait()

        rc = pltpu.make_async_remote_copy(
            src_ref=gbuf.at[cur], dst_ref=gbuf.at[cur],
            send_sem=send_sems.at[i], recv_sem=recv_sems.at[i],
            device_id=(right,),
            device_id_type=pltpu.DeviceIdType.MESH)

        @pl.when(i < P - 1)
        def _():
            rc.start()          # overlap: forward in flight during the dot

        acc = jnp.dot(gbuf[cur], w_ref[...],
                      preferred_element_type=jnp.float32)
        y_ref[pl.ds(cur * m, m), :] = acc.astype(y_ref.dtype)

        @pl.when(i < P - 1)
        def _():
            rc.wait_send()
        return 0

    jax.lax.fori_loop(0, P, step, 0)


def ring_allgather_matmul_local(x_local, w, *, axis_name: str,
                                interpret=None):
    """Per-shard body (call inside shard_map).  x_local: (m, k) this rank's
    row shard; w: (k, n) replicated.  Returns (P*m, n) = full X @ W."""
    P = jax.lax.axis_size(axis_name)
    m, k = x_local.shape
    n = w.shape[1]
    out_dtype = jnp.promote_types(x_local.dtype, w.dtype)
    kernel = functools.partial(_ag_mm_kernel, axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((P * m, n), out_dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # x stays in HBM; DMA'd
            pl.BlockSpec(memory_space=pltpu.VMEM),   # w resident in VMEM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((P, m, k), x_local.dtype),    # gather buffer
            pltpu.SemaphoreType.DMA((P,)),           # per-step send
            pltpu.SemaphoreType.DMA((P,)),           # per-step recv
            pltpu.SemaphoreType.DMA,                 # local staging
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret if interpret is not None else False,
    )(x_local, w)
