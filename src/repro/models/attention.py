"""Attention: GQA/MHA/MQA, qk-norm, RoPE + M-RoPE, blockwise prefill/train
attention (pair-scan online softmax), banded sliding-window attention, and
single-token decode attention over a (possibly sequence-sharded) KV cache or
over paged block pools read in place through a block table.

The pair-scan attention linearizes the (q-chunk, kv-chunk) iteration space to
*only the blocks that contain at least one unmasked element* (lower triangle
for causal; a diagonal band for SWA).  The pair list is computed statically
with numpy, so causal attention costs S(S+1)/2 block matmuls instead of S^2 —
this keeps HLO_FLOPs honest relative to MODEL_FLOPS.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core.comm import (CommMode, TransferDescriptor,
                             register_fusion_target)
from repro.core.sharding import logical_constraint
from repro.core.socket import mem_write
from repro.kernels import ops
from repro.models.layers import _he, rmsnorm

# Fused-transfer descriptor of the tensor-parallel o-projection: the
# partial head products combine on the ring as a matmul+reduce-scatter
# (FUSED_RING under ``use_kernels=True`` with a P2P verdict) instead of a
# serial all-reduce after the matmul.  Archetype "grad_scatter" matches
# the reduce-scatter the compiled HLO exhibits for this lowering.  The
# consumer matmul is registered explicitly even though the descriptor's
# own site label would resolve the self-loop at runtime — commcheck's
# ``fused-target-unregistered`` rule requires every fusion target to
# appear in a register_fusion_target() call, so the chain contract stays
# greppable.
register_fusion_target("attn.o_proj")      # the o-projection matmul
O_PROJ_DESC = TransferDescriptor("grad_scatter", site="attn.o_proj",
                                 fused_with="attn.o_proj")


def o_proj_tp(ctx_local, w_o_local, *, socket, out_dtype=None):
    """Tensor-parallel o-projection inside shard_map over the socket's
    stage axis: ``ctx_local`` (T, H_loc*hd) is this rank's head shard of
    the flattened attention context, ``w_o_local`` (H_loc*hd, d) the
    matching row shard of the output projection.  The per-rank partial
    products are combined hop-by-hop by the fused ring reduce-scatter —
    the transfer the overlap planner prices with the o-matmul's FLOPs —
    returning the (T/P, d) output sequence shard (f32 unless
    ``out_dtype``)."""
    y = socket.matmul_reduce_scatter(ctx_local, w_o_local, O_PROJ_DESC,
                                     hint=CommMode.P2P)
    return y if out_dtype is None else y.astype(out_dtype)


# ------------------------------------------------------------------ RoPE ----

def rope_inv_freq(head_dim: int, theta: float):
    return theta ** (-np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)


def apply_rope(x, pos, theta: float, mrope_sections=None):
    """x: (B, S, N, hd) — N heads or kv-heads.  pos: (B, S) int positions, or
    (B, S, 3) for M-RoPE (t/h/w components; sections are half-dim splits)."""
    B, S, N, hd = x.shape
    inv = jnp.asarray(rope_inv_freq(hd, theta))          # (hd/2,)
    if mrope_sections is not None:
        if pos.ndim == 2:  # text-only stub: t = h = w
            pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
        comps = []
        for idx, sec in enumerate(mrope_sections):
            comps.append(jnp.broadcast_to(pos[..., idx:idx + 1], (B, S, sec)))
        pos_f = jnp.concatenate(comps, axis=-1).astype(jnp.float32)  # (B,S,hd/2)
        ang = pos_f * inv[None, None, :]
    else:
        ang = pos.astype(jnp.float32)[..., None] * inv[None, None, :]  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- parameters ----

def attn_init(key, cfg, dtype=jnp.float32):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "w_q": _he(ks[0], (d, H, hd), dtype, fan_in=d),
        "w_k": _he(ks[1], (d, K, hd), dtype, fan_in=d),
        "w_v": _he(ks[2], (d, K, hd), dtype, fan_in=d),
        "w_o": _he(ks[3], (H, hd, d), dtype, fan_in=H * hd),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((hd,), dtype)
        p["k_scale"] = jnp.ones((hd,), dtype)
    return p


def attn_axes(cfg):
    a = {
        "w_q": ("w_fsdp", "heads", "head_dim"),
        "w_k": ("w_fsdp", "kv_heads", "head_dim"),
        "w_v": ("w_fsdp", "kv_heads", "head_dim"),
        "w_o": ("heads", "head_dim", "w_fsdp"),
    }
    if cfg.qk_norm:
        a["q_scale"] = ("head_dim",)
        a["k_scale"] = ("head_dim",)
    return a


def _project_qkv(params, x, cfg, pos, compute_dtype):
    """x (B,S,d) -> q (B,S,K,G,hd), k/v (B,S,K,hd), rope applied unless
    the arch has no positional encoding (``cfg.rope`` off: Jamba)."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    G = H // K
    xc = x.astype(compute_dtype)
    q = jnp.einsum("bsd,dnh->bsnh", xc, params["w_q"].astype(compute_dtype))
    k = jnp.einsum("bsd,dnh->bsnh", xc, params["w_k"].astype(compute_dtype))
    v = jnp.einsum("bsd,dnh->bsnh", xc, params["w_v"].astype(compute_dtype))
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_scale"]}, q)
        k = rmsnorm({"scale": params["k_scale"]}, k)
    if cfg.rope:
        sections = cfg.mrope_sections if cfg.mrope else None
        q = apply_rope(q, pos, cfg.rope_theta, sections)
        k = apply_rope(k, pos, cfg.rope_theta, sections)
    q = logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = logical_constraint(v, ("batch", "seq", "kv_heads", "head_dim"))
    B, S = x.shape[:2]
    q = q.reshape(B, S, K, G, hd)
    return q, k, v


# ------------------------------------------------- pair-scan block attention

def _block_pairs(n_chunks: int, chunk: int, window: int):
    """Static (i, j) block pairs with >=1 unmasked element, plus a per-pair
    mask id into a SMALL constant mask table.

    Only O(window/chunk) distinct masks exist: off-diagonal interior blocks
    are fully unmasked (id 0), the diagonal is causal (id 1), and band-edge
    blocks share one mask per (i - j) offset.  Using a constant table +
    gather keeps XLA from precomputing a per-pair broadcast mask stack
    (observed: a (n_pairs, B, K, G, c, c) pred tensor carried through the
    scan — gigabytes at 32k prefill).

    window == 0 -> plain causal; else kv in (q - window, q]."""
    pairs = []
    offs_needing_mask = {}
    for i in range(n_chunks):
        for j in range(i + 1):
            if window and (i - j - 1) * chunk >= window:
                continue
            if i == j:
                mask_id = 1
            elif window and (i - j + 1) * chunk - 1 >= window:
                # band edge: some (q, kv) in the block violate the window
                off = i - j
                if off not in offs_needing_mask:
                    offs_needing_mask[off] = 2 + len(offs_needing_mask)
                mask_id = offs_needing_mask[off]
            else:
                mask_id = 0
            pairs.append((i, j, mask_id))
    idx = np.asarray(pairs, dtype=np.int32)

    n_masks = 2 + len(offs_needing_mask)
    pos = np.arange(chunk)
    table = np.ones((n_masks, chunk, chunk), dtype=bool)
    diag = pos[None, :] <= pos[:, None]
    if window:
        diag &= (pos[:, None] - pos[None, :]) < window
    table[1] = diag
    for off, mid in offs_needing_mask.items():
        q_pos = off * chunk + pos[:, None]
        table[mid] = (q_pos - pos[None, :]) < window
    return idx[:, 0], idx[:, 1], idx[:, 2], table


def blockwise_attention(q, k, v, *, chunk=512, window=0):
    out, _ = _blockwise_fwd_impl(q, k, v, chunk=chunk, window=window)
    return out


def _flat_heads(q, k, v):
    """(B,S,K,G,hd) q + (B,S,K,hd) kv -> flat-head (B,S,H,hd) bf16 triples
    with KV repeated.  Flat heads shard over the model axis (unevenly padded
    when H doesn't divide it — 1.8x waste for 9 heads on 16 ranks instead of
    16x replication); the repeat is cheap (KV is the small GQA operand)."""
    B, S, K, G, hd = q.shape
    qf = q.reshape(B, S, K * G, hd).astype(jnp.bfloat16)
    kf = jnp.repeat(k.astype(jnp.bfloat16), G, axis=2)
    vf = jnp.repeat(v.astype(jnp.bfloat16), G, axis=2)
    names = ("batch", "seq", "heads", "head_dim")
    return (logical_constraint(qf, names), logical_constraint(kf, names),
            logical_constraint(vf, names))


def _blockwise_fwd_impl(q, k, v, *, chunk=512, window=0):
    """Causal (optionally banded) attention via online softmax over static
    block pairs, flat-head layout.  q: (B,S,K,G,hd); k, v: (B,S,K,hd).
    Returns (out (B,S,K,G,hd), lse (n,B,H,chunk))."""
    B, S, K, G, hd = q.shape
    H = K * G
    chunk = min(chunk, S)
    assert S % chunk == 0, f"seq {S} % chunk {chunk} != 0"
    n = S // chunk
    i_arr, j_arr, mask_ids, mask_table = _block_pairs(n, chunk, window)
    scale = hd ** -0.5

    qf, kf, vf = _flat_heads(q, k, v)
    masks = jnp.asarray(mask_table)                  # (n_masks, c, c), tiny

    buf_names = (None, "batch", "heads", None)
    m0 = logical_constraint(
        jnp.full((n, B, H, chunk), -jnp.inf, jnp.float32), buf_names)
    l0 = logical_constraint(
        jnp.zeros((n, B, H, chunk), jnp.float32), buf_names)
    o0 = logical_constraint(
        jnp.zeros((n, B, H, chunk, hd), jnp.float32), buf_names + (None,))

    def body(carry, ij):
        m_buf, l_buf, o_buf = carry
        qi, kj, mid = ij
        qc = jax.lax.dynamic_slice_in_dim(qf, qi * chunk, chunk, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(kf, kj * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vf, kj * chunk, chunk, axis=1)
        s = jnp.einsum("bqnh,bsnh->bnqs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = jax.lax.dynamic_index_in_dim(masks, mid, axis=0,
                                            keepdims=False)  # (c, c)
        s = jnp.where(mask[None, None], s, -jnp.inf)

        m_old = m_buf[qi]                                # (B,H,c)
        l_old = l_buf[qi]
        o_old = o_buf[qi]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        alpha = jnp.where(jnp.isneginf(m_old), 0.0, jnp.exp(m_old - m_safe))
        l_new = alpha * l_old + jnp.sum(p, axis=-1)
        o_new = alpha[..., None] * o_old + jnp.einsum(
            "bnqs,bsnh->bnqh", p.astype(jnp.bfloat16), vc,
            preferred_element_type=jnp.float32)
        return (m_buf.at[qi].set(m_new), l_buf.at[qi].set(l_new),
                o_buf.at[qi].set(o_new)), None

    (m_buf, l_buf, o_buf), _ = jax.lax.scan(
        body, (m0, l0, o0),
        (jnp.asarray(i_arr), jnp.asarray(j_arr), jnp.asarray(mask_ids)))
    lse = m_buf + jnp.log(jnp.maximum(l_buf, 1e-37))     # (n,B,H,chunk)
    out = o_buf / jnp.maximum(l_buf[..., None], 1e-37)   # (n,B,H,chunk,hd)
    out = jnp.moveaxis(out, 0, 1)                        # (B,n,H,chunk,hd)
    out = jnp.moveaxis(out, 3, 2)                        # (B,n,chunk,H,hd)
    return out.reshape(B, S, K, G, hd).astype(q.dtype), lse


# ------------------------------------------------------- flash custom_vjp ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, chunk=512, window=0):
    """Blockwise attention with a hand-written backward pass (flash).

    Differentiating the pair-*scan* forward would stack O(S^2) residuals per
    layer; the custom VJP saves only (q, k, v, out, lse) and recomputes each
    block's probabilities in the backward sweep — the flash-attention
    recipe, expressed as the same static block-pair scan."""
    return blockwise_attention(q, k, v, chunk=chunk, window=window)


def _flash_fwd(q, k, v, chunk, window):
    out, lse = _blockwise_fwd_impl(q, k, v, chunk=chunk, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(chunk, window, res, g):
    q, k, v, out, lse = res
    B, S, K, G, hd = q.shape
    H = K * G
    chunk = min(chunk, S)
    n = S // chunk
    i_arr, j_arr, mask_ids, mask_table = _block_pairs(n, chunk, window)
    scale = hd ** -0.5
    masks = jnp.asarray(mask_table)

    qf, kf, vf = _flat_heads(q, k, v)
    gf = logical_constraint(
        g.reshape(B, S, H, hd).astype(jnp.bfloat16),
        ("batch", "seq", "heads", "head_dim"))
    # delta = rowsum(g * out): (B,S,H) -> chunked (n,B,H,c)
    delta = jnp.sum(g.astype(jnp.float32).reshape(B, S, H, hd) *
                    out.astype(jnp.float32).reshape(B, S, H, hd), axis=-1)
    delta = jnp.moveaxis(jnp.moveaxis(delta.reshape(B, n, chunk, H), 1, 0),
                         2, 3)                           # (n,B,H,c)

    buf_names = (None, "batch", "heads", None, None)
    dq0 = logical_constraint(
        jnp.zeros((n, B, H, chunk, hd), jnp.float32), buf_names)
    dk0 = logical_constraint(
        jnp.zeros((n, B, H, chunk, hd), jnp.float32), buf_names)
    dv0 = logical_constraint(
        jnp.zeros((n, B, H, chunk, hd), jnp.float32), buf_names)

    def body(carry, ij):
        dq_buf, dk_buf, dv_buf = carry
        qi, kj, mid = ij
        qc = jax.lax.dynamic_slice_in_dim(qf, qi * chunk, chunk, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(kf, kj * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vf, kj * chunk, chunk, axis=1)
        gc = jax.lax.dynamic_slice_in_dim(gf, qi * chunk, chunk, axis=1)
        lse_c = lse[qi]                                   # (B,H,c)
        delta_c = delta[qi]                               # (B,H,c)
        s = jnp.einsum("bqnh,bsnh->bnqs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = jax.lax.dynamic_index_in_dim(masks, mid, axis=0,
                                            keepdims=False)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jnp.exp(s - lse_c[..., None])                 # (B,H,c,c2)
        pb = p.astype(jnp.bfloat16)
        dv_c = jnp.einsum("bnqs,bqnh->bnsh", pb, gc).astype(jnp.float32)
        dp = jnp.einsum("bqnh,bsnh->bnqs", gc, vc).astype(jnp.float32)
        ds = p * (dp - delta_c[..., None]) * scale        # (B,H,c,c2) f32
        dsb = ds.astype(jnp.bfloat16)
        dq_c = jnp.einsum("bnqs,bsnh->bnqh", dsb, kc).astype(jnp.float32)
        dk_c = jnp.einsum("bnqs,bqnh->bnsh", dsb, qc).astype(jnp.float32)
        return (dq_buf.at[qi].add(dq_c), dk_buf.at[kj].add(dk_c),
                dv_buf.at[kj].add(dv_c)), None

    (dq_buf, dk_buf, dv_buf), _ = jax.lax.scan(
        body, (dq0, dk0, dv0),
        (jnp.asarray(i_arr), jnp.asarray(j_arr), jnp.asarray(mask_ids)))

    def unchunk(buf):  # (n,B,H,c,hd) -> (B,S,H,hd)
        return jnp.moveaxis(jnp.moveaxis(buf, 0, 1), 2, 3).reshape(
            B, S, H, hd)

    dq = unchunk(dq_buf).reshape(B, S, K, G, hd)
    dk = jnp.sum(unchunk(dk_buf).reshape(B, S, K, G, hd), axis=3)
    dv = jnp.sum(unchunk(dv_buf).reshape(B, S, K, G, hd), axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def full_attention(q, k, v, *, window=0):
    """Unchunked causal attention (training path).  The (S, S) score matrix
    is transient under the per-layer remat policy; differentiating it is
    cheap recompute, whereas differentiating the pair-*scan* would stack
    O(S^2) residuals per iteration (observed: 5.4 GB x 1080 loop bodies).

    Sharding: KV is repeated to the full head count so the score tensor can
    shard cleanly over flat heads (classic GQA tensor parallelism).  When
    the head count does not divide the model axis (smollm: 9 heads,
    musicgen: 24, llama4: 40 on a 16-way axis) we fall back to *sequence*
    parallelism over the q dimension — S is divisible for every assigned
    shape, so the score matrix always shards instead of replicating
    (observed otherwise: 9.7 GB/device f32 scores for smollm).

    q: (B,S,K,G,hd); k, v: (B,S,K,hd)."""
    from repro.core.sharding import current_mesh
    B, S, K, G, hd = q.shape
    H = K * G
    qf = q.reshape(B, S, H, hd).astype(jnp.bfloat16)
    kf = jnp.repeat(k.astype(jnp.bfloat16), G, axis=2)
    vf = jnp.repeat(v.astype(jnp.bfloat16), G, axis=2)

    mesh = current_mesh()
    msize = mesh.shape.get("model") if mesh is not None and \
        "model" in mesh.axis_names else 0
    if msize and H % msize == 0:
        s_names = ("batch", "heads", None, None)      # (B, H, Sq, Skv)
        ctx_names = ("batch", None, "heads", "head_dim")
    elif msize and S % msize == 0:
        s_names = ("batch", None, "seq_sp", None)     # shard q rows
        ctx_names = ("batch", "seq_sp", "heads", "head_dim")
    else:
        s_names = ("batch", None, None, None)
        ctx_names = ("batch", None, None, None)

    s = jnp.einsum("bqnh,bsnh->bnqs", qf, kf,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    s = logical_constraint(s, s_names)
    pos = np.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(jnp.asarray(mask)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bnqs,bsnh->bqnh", p.astype(jnp.bfloat16), vf,
                     preferred_element_type=jnp.float32)
    ctx = logical_constraint(ctx, ctx_names)
    return ctx.reshape(B, S, K, G, hd).astype(q.dtype)


def attn_apply(params, x, cfg, pos, *, chunk=512, compute_dtype=jnp.bfloat16,
               window=0, impl="blockwise"):
    """Full train/prefill attention for one block.  Returns (y, (k, v))."""
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(params, x, cfg, pos, compute_dtype)
    if impl == "full" or S <= chunk:
        ctx = full_attention(q, k, v, window=window)
    elif impl == "flash":
        ctx = flash_attention(q, k, v, chunk, window)
    else:
        ctx = blockwise_attention(q, k, v, chunk=chunk, window=window)
    # bf16-out o-projection: its model-axis all-reduce moves half the bytes
    # of the f32 version (observed 3.7 TB/device/step of f32 all-reduce
    # wire on qwen2-vl train before this change) — §Perf iteration C1.
    y = jnp.einsum("bskgh,kghd->bsd",
                   ctx.astype(compute_dtype),
                   params["w_o"].astype(compute_dtype).reshape(K, H // K, hd, d)
                   ).astype(x.dtype)
    y = mem_write(y, "attn_output", ("batch", "seq", "embed"))
    # tagged for the save_collectives remat policy (§Perf C2)
    y = checkpoint_name(y, "post_collective")
    return y, (k, v)


# ------------------------------------------------------- decode attention ----

def decode_attn_apply(params, x, cfg, cache, pos_scalar, *,
                      compute_dtype=jnp.bfloat16, window=0):
    """One-token decode.  x: (B, 1, d).  cache: {"k","v"}: (B, Skv, K, hd)
    (ring buffer of size `window` when window>0, else full seq).  pos_scalar:
    scalar int32 absolute position of the new token — or a (B,) int32 vector
    of per-row positions (continuous batching: each slot in the batch is a
    different request at a different depth).  Returns (y, new_cache).

    Slots past a row's position are masked out of the softmax: a freshly
    allocated (zero) cache tail must not contribute exp(0-m) mass to the
    denominator.  For a full ring (pos + 1 >= Skv) every slot is valid and
    the mask is the identity, so the pre-filled single-request contract is
    unchanged.

    The KV cache's Skv dim carries the "kv_seq" logical axis (sequence-sharded
    over the model axis by the serve rules); softmax reductions over it lower
    to small all-reduces — the paper's sync-region pattern: tiny control
    payloads (m, l statistics) on the fast path, bulk (cache) stays put.
    """
    B = x.shape[0]
    pos_b = jnp.reshape(
        jnp.broadcast_to(jnp.asarray(pos_scalar, jnp.int32), (B,)), (B, 1))
    q, k_new, v_new = _project_qkv(params, x, cfg, pos_b, compute_dtype)

    Skv = cache["k"].shape[1]
    slot = jnp.mod(pos_b, Skv) if window else jnp.minimum(pos_b, Skv - 1)
    # One-hot update instead of dynamic-update-slice: a DUS at a dynamic
    # index on the sequence-SHARDED cache dim forces GSPMD into full-cache
    # gather/select patterns; the where(iota == slot) form shards cleanly
    # (each shard compares its local iota against the global slot).
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (1, Skv, 1, 1), 1)
    sel = iota_s == slot[:, :, None, None]
    k_cache = jnp.where(sel, k_new.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(sel, v_new.astype(cache["v"].dtype), cache["v"])
    k_cache = logical_constraint(k_cache, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v_cache = logical_constraint(v_cache, ("batch", "kv_seq", "kv_heads", "head_dim"))

    # slots written so far: min(pos + 1, Skv) — the whole ring once full
    # (the pre-filled serve_step contract), a prefix while a paged/slot
    # cache is still growing
    n_valid = jnp.minimum(pos_b[:, :1] + 1, Skv)          # (B, 1)
    ctx = _decode_context(q, k_cache, v_cache, n_valid, compute_dtype)
    return _decode_out(params, ctx, x, cfg, compute_dtype), \
        {"k": k_cache, "v": v_cache}


def _decode_context(q, k_cache, v_cache, n_valid, compute_dtype):
    """Softmax attention of one query token per row over the first
    ``n_valid`` (B, 1) positions of a contiguous cache.  q: (B,1,K,G,hd);
    caches: (B, Skv, K, hd).  Returns the f32 context (B,1,K,G,hd)."""
    hd, Skv = q.shape[-1], k_cache.shape[1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(compute_dtype),
                   k_cache.astype(compute_dtype),
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, Skv), 1)
             < n_valid)[:, None, None, None, :]           # (B,1,1,1,Skv)
    s = jnp.where(valid, s, -jnp.inf)                     # (B,K,G,1,Skv)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bkgqs,bskh->bqkgh", (p / l).astype(compute_dtype),
                      v_cache.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def _decode_out(params, ctx, x, cfg, compute_dtype):
    """The o-projection of a (B,1,K,G,hd) decode context."""
    d = x.shape[-1]
    K, G, hd = ctx.shape[2:]
    y = jnp.einsum("bqkgh,kghd->bqd", ctx.astype(compute_dtype),
                   params["w_o"].astype(compute_dtype).reshape(K, G, hd, d),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return logical_constraint(y, ("batch", None, "embed"))


class PagedKV(NamedTuple):
    """One block kind's paged KV history, read and written in place.

    ``k``/``v``: the whole stacked block pools (layers, blocks, block_size,
    K, hd); ``tables``: the (B, max_blocks) int32 block table; ``layer``:
    the scan's layer index (None outside the layer scan)."""
    k: Any
    v: Any
    tables: Any
    layer: Any = None


def _write_rows(pool, layer, tables, pos, rows):
    """Land each slot's new (K, hd) row at ``(layer, tables[b, pos // bs],
    pos % bs)`` of the stacked pool; nothing else of the pool moves.  An
    inactive slot's all-null table row lands it on the null block."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    return pool.at[layer, blk, pos % bs].set(rows.astype(pool.dtype))


def paged_decode_attn_apply(params, x, cfg, cache: PagedKV, pos, *,
                            compute_dtype=jnp.bfloat16):
    """One-token decode over a paged, unwindowed KV history.  x: (B,1,d);
    pos: (B,) int32.  The new token's K and V are written into the pools
    first, so each slot attends over its first ``pos + 1`` positions,
    read through the block table: by the Pallas kernel on a TPU, and off
    it by the same softmax as :func:`decode_attn_apply` over a ``jnp.take``
    of the layer's table blocks.  Returns (y, cache with the new pools)."""
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _project_qkv(params, x, cfg, pos[:, None],
                                   compute_dtype)
    k_pool = _write_rows(cache.k, cache.layer, cache.tables, pos, k_new[:, 0])
    v_pool = _write_rows(cache.v, cache.layer, cache.tables, pos, v_new[:, 0])
    lengths = pos + 1
    if jax.default_backend() == "tpu":
        ctx = ops.paged_decode_attention(
            q.reshape(B, H, hd).astype(compute_dtype), k_pool, v_pool,
            cache.tables, lengths, cache.layer).reshape(q.shape)
    else:
        def view(pool):                                # (B, mb*bs, K, hd)
            return jnp.take(pool[cache.layer], cache.tables, axis=0).reshape(
                B, -1, K, hd)
        ctx = _decode_context(q, view(k_pool), view(v_pool), lengths[:, None],
                              compute_dtype)
    return _decode_out(params, ctx, x, cfg, compute_dtype), \
        cache._replace(k=k_pool, v=v_pool)
