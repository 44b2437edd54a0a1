"""Pure-jnp oracles for every kernel (the correctness contracts)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def allgather_matmul_ref(x_shards, w):
    """x_shards: (P, m, k) stacked row shards; w: (k, n).
    Every rank's expected output: concat(shards) @ w -> (P*m, n)."""
    P, m, k = x_shards.shape
    full = x_shards.reshape(P * m, k)
    return jnp.dot(full, w, preferred_element_type=jnp.float32)


def reducescatter_matmul_ref(x_shards, w_shards):
    """x_shards: (P, m, k_p); w_shards: (P, k_p, n).  Rank r's expected
    output: rows [r*m/P, (r+1)*m/P) of sum_p(x_p @ w_p) -> (P, m/P, n)."""
    P, m, kp = x_shards.shape
    full = jnp.einsum("pmk,pkn->mn", x_shards.astype(jnp.float32),
                      w_shards.astype(jnp.float32))
    return full.reshape(P, m // P, -1)


def multicast_ref(x_src, P):
    """Every rank receives the source payload."""
    return jnp.broadcast_to(x_src[None], (P,) + x_src.shape)


def dma_stream_ref(x, scale):
    xf = x.astype(jnp.float32) * scale
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths, layer):
    """Gather the layer's table blocks into the contiguous per-slot view,
    then a plain float32 softmax over each slot's first ``lengths`` keys.
    q: (n, H, hd); pools: (L, blocks, bs, K, hd); tables: (n, mb);
    lengths: (n,).  Returns (n, H, hd) float32 (NaN for a length of 0)."""
    n, H, hd = q.shape
    K = k_pool.shape[3]

    def view(pool):                                   # (n, mb*bs, K, hd)
        return jnp.take(pool[layer], tables, axis=0).reshape(
            n, -1, K, hd).astype(jnp.float32)

    k, v = view(k_pool), view(v_pool)
    qf = q.astype(jnp.float32).reshape(n, K, H // K, hd)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("nkgd,ntkd->nkgt", qf, k, precision=hi) * hd ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nkgt,ntkd->nkgd", p, v, precision=hi).reshape(
        n, H, hd)
