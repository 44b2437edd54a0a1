"""Paged decode attention read in place through the block table.

One query token per slot attends over that slot's KV history, which lives
in a stacked block pool ``(layers, blocks, block_size, kv_heads,
head_dim)`` and is found through the slot's row of the block table.  The
kernel never builds the contiguous per-slot view: it DMAs each live block
of one layer from HBM into VMEM, ``blocks_per_chunk`` blocks per chunk,
double-buffered (chunk c+1's IDMA is issued before chunk c is consumed),
and folds every chunk into an online softmax kept in f32.  The pipeline
runs on across slots: a slot's last chunk starts the next slot's first.
A slot reads ``ceil(length / block_size)`` blocks; blocks past its length
are neither copied nor counted.

The whole stacked pool and the layer index go in (a layer sliced out
inside the decode scan would be materialized for the custom call).  In
VMEM a block is ``block_size * kv_heads`` rows of ``head_dim``; row r
holds token ``r // kv_heads`` of kv head ``r % kv_heads``.  All query
heads score against all rows of a chunk in one matmul, and a mask keeps
each query head to the rows of its own kv head (GQA: the ``G = H / K``
query heads of kv head ``h`` are rows ``h * G ... h * G + G - 1``).
That costs the MXU ``kv_heads`` times the useful products, which at one
query token per slot is far below the bytes' time, and needs no
transpose or strided read of the block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens per chunk the default chunking aims at
CHUNK_TOKENS = 128


def _kernel(bs, K, G, nb, mb, scale,
            layer_ref, len_ref, tab_ref,        # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm,                # (1, H, hd); pools in HBM
            o_ref,                              # (1, H, hd)
            kbuf, vbuf, sems):                  # (2, nb, bs*K, hd) x 2
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    H, hd = q_ref.shape[1], q_ref.shape[2]
    rows = nb * bs * K
    tok_per_chunk = nb * bs

    def n_blocks(s):
        return jnp.minimum(lax.div(len_ref[s] + bs - 1, bs), mb)

    def n_chunks_of(s):
        return lax.div(n_blocks(s) + nb - 1, nb)

    # chunks run back to back across slots, alternating buffers: slot b's
    # chunk c sits in buffer (chunks of the slots before b + c) % 2
    first = lax.fori_loop(0, b, lambda s, acc: acc + n_chunks_of(s), 0)
    length = len_ref[b]
    n_chunks = n_chunks_of(b)

    def dma(s, c, buf, start):
        nbs = n_blocks(s)
        for i in range(nb):
            j = c * nb + i

            @pl.when(j < nbs)
            def _():
                blk = tab_ref[s * mb + j]
                for t, (src, dst) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                    cp = pltpu.make_async_copy(src.at[layer, blk],
                                               dst.at[buf, i],
                                               sems.at[t, buf])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

    q = q_ref[0]                                          # (H, hd)
    col = lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    row = lax.broadcasted_iota(jnp.int32, (H, rows), 0)
    own_head = lax.rem(col, K) == lax.div(row, G)
    col_tok = lax.div(col, K)
    v_tok = lax.div(lax.broadcasted_iota(jnp.int32, (rows, hd), 0), K)

    def body(c, carry):
        m, l, acc = carry
        buf = lax.rem(first + c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            dma(b, c + 1, 1 - buf, start=True)

        # the last chunk starts the next slot's first one
        @pl.when((c + 1 == n_chunks) & (b + 1 < n_slots))
        def _():
            dma(b + 1, 0, 1 - buf, start=True)

        dma(b, c, buf, start=False)
        left = length - c * tok_per_chunk       # live tokens from here on
        k = kbuf[buf].reshape(rows, hd)
        v = vbuf[buf].reshape(rows, hd)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        # rows past the length hold stale VMEM: select, never add, so no
        # NaN they might hold reaches the sums
        s = jnp.where(own_head & (col_tok < left), s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        v = jnp.where(v_tok < left, v, jnp.zeros_like(v))
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    # a slot's first chunk was started by the slot before it, unless that
    # one had nothing to read (or this is the first slot)
    @pl.when((n_chunks > 0) &
             ((b == 0) | (n_chunks_of(jnp.maximum(b - 1, 0)) == 0)))
    def _():
        dma(b, 0, lax.rem(first, 2), start=True)

    m0 = jnp.full((H, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, hd), jnp.float32)
    _, l, acc = lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths, layer, *,
                           blocks_per_chunk=None, interpret=None):
    """softmax(q k^T / sqrt(hd)) v for one query token per slot, over the
    first ``lengths[slot]`` positions of the slot's paged history.

    q: (n_slots, H, hd), heads ordered (kv head, group); k_pool, v_pool:
    (layers, blocks, block_size, K, hd); tables: (n_slots, max_blocks)
    int32 physical block ids; lengths: (n_slots,) int32; layer: scalar
    int32.  Scores and softmax accumulate in f32; the output has q's
    dtype.  A slot of length 0 reads nothing and returns zeros."""
    n_slots, H, hd = q.shape
    L, NB, bs, K, hd_k = k_pool.shape
    mb = tables.shape[1]
    assert hd == hd_k and H % K == 0 and v_pool.shape == k_pool.shape
    nb = blocks_per_chunk or max(1, min(mb, CHUNK_TOKENS // bs))
    # (bs, K) -> bs*K rows: the pool's trailing dims are contiguous, so
    # the merge is a relabelling, not a copy
    k_rows = k_pool.reshape(L, NB, bs * K, hd)
    v_rows = v_pool.reshape(L, NB, bs * K, hd)
    kernel = functools.partial(_kernel, bs, K, H // K, nb, mb, hd ** -0.5)
    slot_spec = pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[slot_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((2, nb, bs * K, hd), k_pool.dtype),
                pltpu.VMEM((2, nb, bs * K, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        # sequential slots: each one's first chunk is in flight from the
        # slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret if interpret is not None else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      q, k_rows, v_rows)
