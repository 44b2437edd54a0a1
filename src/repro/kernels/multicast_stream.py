"""Multicast stream: one source, chunked store-and-forward on a ring (C2).

The multicast NoC forks a message at routers so one injection serves all
destinations; on the ICI ring the analogue is store-and-forward pipelining:
the source streams the payload in chunks and every member forwards each
chunk to its right neighbour — after a P-hop latency fill, all links carry
payload concurrently (the wormhole/burst pipelining of Fig. 6).  Total time
~ (chunks + P) * chunk_time instead of P * message_time for repeated
unicasts.

The schedule is *uniform*: the ring runs R = P + n_chunks - 1 rounds and
every device issues exactly one remote DMA per round.  At round r the
device ``dist`` hops from the source forwards chunk ``c = r - dist``; when
that chunk index is out of range (pipeline fill/drain) or the device is the
last ring member, it still sends — into the receiver's scratch slot, so the
payload is untouched.  Uniformity makes every device receive exactly one
message per round, so per-round semaphores keep the pipeline overrun-safe
without per-device branching of the DMA sequence (the deadlock-freedom
argument the paper inherits from [18]).  The fill/drain sends into the
scratch slot are wasted link traffic: a schedule that sends only real
chunks would need per-device receive counts instead.

Chunk granularity doubles as flow control: a member holds at most the one
chunk it has not yet forwarded (the consumption assumption, C1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mcast_kernel(axis_name, src, n_chunks, x_hbm, y_ref, buf, send_sems,
                  recv_sems, stage_sem):
    p = jax.lax.axis_index(axis_name)
    P = jax.lax.axis_size(axis_name)
    right = jax.lax.rem(p + 1, P)
    dist = jax.lax.rem(p - src + P, P)      # hops from the source
    rows = y_ref.shape[0] // n_chunks
    trash = n_chunks                        # scratch slot for fill/drain sends
    R = P + n_chunks - 1                    # total forwarding rounds

    @pl.when(dist == 0)
    def _():
        # source: stage payload chunks into the ring buffer (local IDMA)
        def stage(c, _):
            cp = pltpu.make_async_copy(
                x_hbm.at[pl.ds(c * rows, rows), :], buf.at[c], stage_sem)
            cp.start()
            cp.wait()
            return 0
        jax.lax.fori_loop(0, n_chunks, stage, 0)

    def step(r, _):
        @pl.when(r > 0)
        def _():
            # exactly one slot-sized message lands per device per round
            pltpu.make_async_copy(buf.at[trash], buf.at[trash],
                                  recv_sems.at[r - 1]).wait()

        c = r - dist                        # chunk scheduled for this round
        real = (c >= 0) & (c < n_chunks) & (dist < P - 1)
        c_src = jnp.clip(c, 0, n_chunks - 1)
        dst_slot = jnp.where(real, c_src, trash)
        rc = pltpu.make_async_remote_copy(
            src_ref=buf.at[c_src], dst_ref=buf.at[dst_slot],
            send_sem=send_sems.at[r], recv_sem=recv_sems.at[r],
            device_id=(right,),
            device_id_type=pltpu.DeviceIdType.MESH)
        rc.start()
        rc.wait_send()
        return 0

    jax.lax.fori_loop(0, R, step, 0)
    # drain the final round's arrival, then publish the assembled payload
    pltpu.make_async_copy(buf.at[trash], buf.at[trash],
                          recv_sems.at[R - 1]).wait()

    def publish(c, _):
        y_ref[pl.ds(c * rows, rows), :] = buf[c]
        return 0

    jax.lax.fori_loop(0, n_chunks, publish, 0)


def multicast_stream_local(x, *, axis_name: str, src: int = 0,
                           n_chunks: int = 4, interpret=None):
    """Per-shard body (call inside shard_map).  ``x``: (m, n) payload (only
    the source rank's value is used).  Returns (m, n) on every rank."""
    m, n = x.shape
    assert m % n_chunks == 0, f"rows {m} % chunks {n_chunks} != 0"
    P = jax.lax.axis_size(axis_name)
    n_rounds = P + n_chunks - 1
    kernel = functools.partial(_mcast_kernel, axis_name, src, n_chunks)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n_chunks + 1, m // n_chunks, n), x.dtype),
            pltpu.SemaphoreType.DMA((n_rounds,)),
            pltpu.SemaphoreType.DMA((n_rounds,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret if interpret is not None else False,
    )(x)
