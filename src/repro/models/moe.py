"""Mixture-of-Experts with communication-mode-selectable dispatch.

This is the framework-level reproduction of the paper's multicast NoC (C2)
and per-transfer mode control (C4):

* ``mode="mem"`` — the *shared-memory baseline* (paper Fig. 6 baseline):
  token activations are replicated across the model axis (the "round trip
  through memory"); every expert-owning shard locally selects the tokens
  routed to its experts and the partial outputs are combined with a full
  ``psum`` over the model axis.

* ``mode="mcast"`` — the *multicast/P2P path*: token activations live
  sequence-sharded on the model axis (SP); each source shard packs, per
  expert, a capacity-bounded buffer of routed tokens and a single
  ``all_to_all`` forwards every buffer to its expert's owner — one producer
  burst fanned out to k consumers, exactly the paper's multicast transfer
  (top-1 = unicast P2P, top-k = multicast).  Results return by the mirrored
  ``all_to_all``; no psum is needed.

Both paths share routing and expert compute, so tests assert they agree.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.comm import (CommMode, TransferDescriptor,
                             register_fusion_target)
from repro.core.socket import socket_for_axis
from repro.models.layers import _he

# the two transfers of the multicast dispatch path, as issued through the
# socket: the plan key is "moe_dispatch" for both (the combine all_to_all
# is the mirrored dispatch — the HLO analysis prices them under the same
# archetype); distinct site labels keep them apart in the issue log.  The
# dispatch declares the expert FFN as its consumer matmul (fused_with):
# the overlap objective prices its transfer hidden behind the expert
# einsums, and ``AcceleratorSocket.dispatch_expert_ffn`` dispatches the
# whole chain (dispatch -> FFN -> combine) as the ring pipeline when
# kernels are enabled — hop s+1 streams while slab s feeds the expert
# matmuls.  With kernels off the chain lowers the serial all_to_all pair
# (bit-identical; ``fused=False`` in the issue log).  The combine feeds
# the token scatter-add — no matmul of its own — so it stays undeclared
# and rides the chain's mirrored hop.
register_fusion_target("moe.expert_ffn")   # the expert gate/up/down einsums
DISPATCH_DESC = TransferDescriptor("moe_dispatch", site="moe.dispatch",
                                   fused_with="moe.expert_ffn")
COMBINE_DESC = TransferDescriptor("moe_dispatch", site="moe.combine")
COMBINE_REDUCE_DESC = TransferDescriptor("grad_reduce", site="moe.combine_psum")


def moe_init(key, cfg, dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _he(ks[0], (d, E), dtype),
        "w_gate": _he(ks[1], (E, d, ff), dtype, fan_in=d),
        "w_up": _he(ks[2], (E, d, ff), dtype, fan_in=d),
        "w_down": _he(ks[3], (E, ff, d), dtype, fan_in=ff),
    }


def moe_axes(cfg):
    return {
        "router": (None, None),
        "w_gate": ("experts", "w_fsdp", None),
        "w_up": ("experts", "w_fsdp", None),
        "w_down": ("experts", None, "w_fsdp"),
    }


def _route(router_w, x_flat, k):
    """Returns (gates (N, k), idx (N, k), aux_loss scalar)."""
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # GShard aux loss: E * mean_e(frac_tokens_e * mean_prob_e)
    E = probs.shape[-1]
    onehot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return gates, idx, aux


def _expert_ffn(wg, wu, wd, toks, compute_dtype):
    """toks (E_loc, C, d) through per-expert gated MLP."""
    t = toks.astype(compute_dtype)
    g = jnp.einsum("ecd,edf->ecf", t, wg.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", t, wu.astype(compute_dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(compute_dtype))


def _select_for_experts(x_flat, gates, idx, experts, capacity):
    """For each expert id in `experts` (static int array), pick its top-
    `capacity` routed tokens by gate weight.

    Returns toks (E_sel, C, d), src (E_sel, C) token indices, w (E_sel, C)
    gate weights (0 where slot unused)."""
    N = x_flat.shape[0]

    def one_expert(e):
        # gate of token n for expert e (0 if not routed there)
        match = (idx == e)                           # (N, k)
        g = jnp.sum(jnp.where(match, gates, 0.0), axis=-1)   # (N,)
        w, src = jax.lax.top_k(g, capacity)          # capacity <= N enforced by caller
        valid = w > 0
        toks = jnp.take(x_flat, src, axis=0) * valid[:, None].astype(x_flat.dtype)
        return toks, src, jnp.where(valid, w, 0.0)

    return jax.vmap(one_expert)(experts)


def moe_apply(params, x, cfg, *, mode: str = "mem",
              model_axis: Optional[str] = "model",
              compute_dtype=jnp.bfloat16,
              use_kernels: bool = False, interpret=None):
    """x: (B, S_local_or_global, d) *inside* shard_map when model_axis is an
    active axis name, or a plain array when model_axis is None (single-device
    smoke-test path).  Returns (y, aux_loss).

    ``use_kernels``/``interpret`` forward to the socket: with kernels on,
    the mcast path's dispatch->FFN->combine chain dispatches as the ring
    pipeline (``AcceleratorSocket.dispatch_expert_ffn``); off, the same
    chain lowers the serial all_to_all pair — identical numbers."""
    B, S, d = x.shape
    k = cfg.moe.top_k
    E = cfg.moe.n_experts
    x_flat = x.reshape(B * S, d)
    N = B * S

    gates, idx, aux = _route(params["router"], x_flat, k)

    if model_axis is None:
        M, rank, E_loc = 1, 0, E
    else:
        M = jax.lax.axis_size(model_axis)
        rank = jax.lax.axis_index(model_axis)
        assert E % M == 0, f"{E} experts not divisible by model axis {M}"
        E_loc = E // M

    capacity = max(1, min(N, int(cfg.moe.capacity_factor * N * k / E)))

    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]

    if mode == "mem" or model_axis is None:
        # shared-memory baseline: x is replicated over the model axis; each
        # shard computes its local experts' tokens and psums the combine.
        local_ids = jnp.arange(E_loc) + rank * E_loc
        toks, src, w = _select_for_experts(x_flat, gates, idx, local_ids, capacity)
        out_toks = _expert_ffn(wg, wu, wd, toks, compute_dtype)
        out_toks = out_toks * w[..., None].astype(out_toks.dtype)
        y = jnp.zeros((N, d), jnp.float32).at[src.reshape(-1)].add(
            out_toks.reshape(-1, d).astype(jnp.float32))
        if model_axis is not None:
            # bf16 combine: each token has at most top_k contributions, so
            # the psum is a short sum — half the wire/buffer of f32 (§Perf A3);
            # a reduction cannot combine in flight, so the socket pins it
            # to the memory path regardless of the plan
            sock = socket_for_axis(model_axis)
            y = sock.reduce(y.astype(jnp.bfloat16), COMBINE_REDUCE_DESC)
        return y.reshape(B, S, d).astype(x.dtype), aux

    if mode == "mcast":
        # multicast dispatch: pack per-expert capacity buffers for ALL
        # experts from the local (sequence-sharded) tokens, then forward
        # each buffer to the shard owning that expert.  The whole
        # dispatch -> expert FFN -> combine chain is ONE socket dispatch
        # (``dispatch_expert_ffn``): each source's per-expert buffers fan
        # out to the expert owners — the paper's multicast transfer
        # (top-1 = unicast degeneracy) — run as the overlapped ring
        # pipeline when kernels are on, the serial all_to_all pair
        # otherwise; the caller's mode choice rides in as the hint when
        # no plan is active.
        all_ids = jnp.arange(E)
        toks, src, w = _select_for_experts(x_flat, gates, idx, all_ids, capacity)
        sock = socket_for_axis(model_axis, use_kernels=use_kernels,
                               interpret=interpret)
        back = sock.dispatch_expert_ffn(
            toks.reshape(M, E_loc, capacity, d),
            lambda t: _expert_ffn(wg, wu, wd, t, compute_dtype),
            DISPATCH_DESC, COMBINE_DESC, hint=CommMode.MCAST)
        # back: (M, E_loc, C, d) == outputs for MY tokens, expert-major.
        back = back.reshape(E, capacity, d)
        back = back * w[..., None].astype(back.dtype)
        y = jnp.zeros((N, d), jnp.float32).at[src.reshape(-1)].add(
            back.reshape(-1, d).astype(jnp.float32))
        return y.reshape(B, S, d).astype(x.dtype), aux

    raise ValueError(f"unknown moe mode: {mode}")
