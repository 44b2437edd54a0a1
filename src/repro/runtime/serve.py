"""Serving-step factories: prefill and single-token decode.

Decode shardings follow SERVE_RULES: KV caches are *sequence-sharded* over
the model axis (all 16 TP ranks hold a slice of every head's history) with
partial-softmax statistics combined by small all-reduces — the bulk payload
(the cache) never moves; only the tiny (m, l) statistics cross the fabric.
This is the paper's C3 split (sync region vs. bulk) applied to attention.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.comm import CommMode
from repro.core.sharding import (logical_to_pspec, resolve_rules,
                                 rule_gated_issued_mode, use_rules)
from repro.core.socket import record_implicit_issue
from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.runtime import kv_blocks as KB
from repro.runtime.train import SERVE_RULES, _axes_leaf


def resolved_serve_rules(comm_plan, rules=None):
    """Planner -> sharding feedback for the serve rules (see
    ``runtime.train.resolved_train_rules``): e.g. the 2-D weight sharding's
    ``w_fsdp = "data"`` gather is dropped when the weight transfer plans to
    MCAST.  Returns ``(resolved_rules, overlay)``."""
    return resolve_rules(comm_plan, dict(rules or SERVE_RULES))


def serve_shardings(cfg: ArchConfig, mesh, B: int, skv: int, rules=None,
                    param_dtype=jnp.bfloat16):
    """Shape-aware shardings for (params, cache, tokens)."""
    rules = rules or SERVE_RULES
    p_axes = T.param_axes(cfg)
    p_specs = jax.eval_shape(
        lambda: T.init_params(jax.random.key(0), cfg, param_dtype))

    def to_sh(names, spec=None):
        shape = spec.shape if spec is not None else None
        return NamedSharding(mesh, logical_to_pspec(names, rules, mesh,
                                                    shape=shape))

    param_sh = jax.tree.map(to_sh, p_axes, p_specs, is_leaf=_axes_leaf)
    cache_specs = T.make_cache(cfg, B, skv, as_specs=True)
    cache_sh = jax.tree.map(to_sh, T.cache_axes(cfg, B, skv), cache_specs,
                            is_leaf=_axes_leaf)
    tok_sh = NamedSharding(mesh, logical_to_pspec(("batch", None), rules, mesh,
                                                  shape=(B, 1)))
    return param_sh, cache_sh, tok_sh


def grow_caches(cfg: ArchConfig, caches, prompt_len: int, gen: int):
    """Grow contiguous prefill caches to hold ``gen`` decoded tokens.

    Only full-sequence attention history grows, and it is classified by
    the *logical axis names* of ``transformer.cache_axes`` (via the
    paged-layout leaf specs) — never by a shape test like
    ``leaf.shape[-3] == prompt_len``, which false-positives whenever an
    unrelated cache dim (e.g. a conv-state depth) happens to equal the
    prompt length.  Ring leaves (``window <= prompt_len``) stay at
    ``prompt_len`` and wrap (the decode contract); recurrent slot state
    never grows.  The pad happens once — callers must not re-pad per
    decode step (an O(S^2) copy)."""
    layout = KB.paged_layout(cfg, n_slots=1, prompt_len=prompt_len,
                             max_new_tokens=gen, block_size=1)

    def grow(sp, leaf):
        if not sp.paged:
            return leaf
        ax = sp.kv_ax
        pad = [(0, 0)] * leaf.ndim
        pad[ax] = (0, gen)
        return jnp.pad(leaf, pad)

    return jax.tree.map(grow, layout.specs, caches,
                        is_leaf=KB._spec_is_leaf)


# The compiler-issued weight gather is logged inline at each step factory
# (trace time, literal site= and reason= so commcheck's extractor admits
# the sites into the coverage universe): the 2-D sharding's per-layer
# gather goes direct only once the plan's verdict cleared the ``w_fsdp``
# rule gate.


def make_prefill_step(cfg: ArchConfig, flags: T.RunFlags, mesh=None,
                      rules=None, comm_plan=None):
    rules = rules or SERVE_RULES

    def step(params, tokens):
        with use_rules(rules, mesh, comm_plan=comm_plan):
            if comm_plan is not None:
                record_implicit_issue(
                    "weights", planned=comm_plan.mode("weights"),
                    issued=rule_gated_issued_mode("weights", comm_plan,
                                                  rules),
                    impl="xla_all_gather", site="prefill.weights_gather",
                    reason="w_fsdp gate not cleared: gather rides memory")
            return T.prefill(params, tokens, cfg, flags)

    return step


def _decode_downgrades(cfg: ArchConfig, flags: T.RunFlags, comm_plan):
    """MoE mcast dispatch needs a sequence dimension to shard; a single
    decode position has none, so decode always uses the MEM path (C4: mode
    choice is per-transfer, and this transfer's best mode differs from
    prefill's).  The downgrade is *recorded*, not silent: a
    machine-readable ``decode_no_seq_dim`` reason lands in the issue log
    under the descriptor's canonical ``moe.dispatch`` site — epoch-scoped
    when the caller builds the step inside an ``issue_epoch`` (the engine
    binds decode under ``issue_epoch("decode")``, keying the record as
    ``moe.dispatch@decode``) — so ``mismatched_sites()`` and the
    ``--against-artifact`` coverage gate resolve it through the same
    descriptor the fused dispatch chain declares."""
    if flags.moe_mode != "mem":
        # dataclasses.replace, never RunFlags(**{**flags.__dict__, ...}):
        # the frozen dataclass's __dict__ round-trip breaks under slots
        # and silently copies stale derived state
        flags = dataclasses.replace(flags, moe_mode="mem")
    if comm_plan is not None and cfg.moe is not None:
        planned = comm_plan.mode("moe_dispatch")
        comm_plan = comm_plan.with_mode("moe_dispatch", CommMode.MEM)
        record_implicit_issue(
            "moe_dispatch", planned=planned, issued=CommMode.MEM,
            impl="decode_downgrade", reason="decode_no_seq_dim",
            site="moe.dispatch")
    elif comm_plan is not None:
        comm_plan = comm_plan.with_mode("moe_dispatch", CommMode.MEM)
    return flags, comm_plan


def make_decode_step(cfg: ArchConfig, flags: T.RunFlags, mesh=None,
                     rules=None, comm_plan=None):
    rules = rules or SERVE_RULES
    flags, comm_plan = _decode_downgrades(cfg, flags, comm_plan)

    def step(params, token, pos, caches):
        with use_rules(rules, mesh, comm_plan=comm_plan):
            if comm_plan is not None:
                record_implicit_issue(
                    "weights", planned=comm_plan.mode("weights"),
                    issued=rule_gated_issued_mode("weights", comm_plan,
                                                  rules),
                    impl="xla_all_gather", site="decode.weights_gather",
                    reason="w_fsdp gate not cleared: gather rides memory")
            return T.decode_step(params, token, pos, caches, cfg, flags)

    return step


def make_batched_decode_step(cfg: ArchConfig, flags: T.RunFlags, mesh=None,
                             rules=None, comm_plan=None):
    """Continuously batched decode over contiguous caches: ``pos`` is a
    (B,) int32 vector — every batch row is its own request at its own
    depth, with cache slots past a row's position masked out of the
    softmax (see ``attention.decode_attn_apply``)."""
    rules = rules or SERVE_RULES
    flags, comm_plan = _decode_downgrades(cfg, flags, comm_plan)

    def step(params, tokens, pos, caches):
        with use_rules(rules, mesh, comm_plan=comm_plan):
            if comm_plan is not None:
                record_implicit_issue(
                    "weights", planned=comm_plan.mode("weights"),
                    issued=rule_gated_issued_mode("weights", comm_plan,
                                                  rules),
                    impl="xla_all_gather", site="decode.weights_gather",
                    reason="w_fsdp gate not cleared: gather rides memory")
            return T.decode_step(params, tokens, pos, caches, cfg, flags)

    return step


def make_paged_decode_step(cfg: ArchConfig, flags: T.RunFlags,
                           layout: "KB.PagedLayout", mesh=None, rules=None,
                           comm_plan=None):
    """Block-table decode for the serving engine.

    ``step(params, tokens, pos, pools, tables)``: tokens ``(n_slots, 1)``,
    pos ``(n_slots,)``, ``tables`` the ``(n_slots, max_blocks)`` int32
    block table; returns ``(logits, pools)``.  Growing a request's cache
    is a host-side table update — the step never retraces.

    Where :func:`decodes_in_place` holds, each layer writes its new K and
    V rows into the pools and reads each slot's live blocks through the
    table (donate ``pools`` and nothing else of them is copied).
    Otherwise — rings, recurrent state, a windowed history, or a mesh,
    under which the kernel would not partition — the step gathers the
    contiguous per-slot view, runs the contiguous batched decode, and
    scatters back the block holding each slot's write position."""
    rules = rules or SERVE_RULES
    flags, comm_plan = _decode_downgrades(cfg, flags, comm_plan)
    in_place = decodes_in_place(layout, mesh)

    def step(params, tokens, pos, pools, tables):
        with use_rules(rules, mesh, comm_plan=comm_plan):
            if comm_plan is not None:
                record_implicit_issue(
                    "weights", planned=comm_plan.mode("weights"),
                    issued=rule_gated_issued_mode("weights", comm_plan,
                                                  rules),
                    impl="xla_all_gather", site="decode.weights_gather",
                    reason="w_fsdp gate not cleared: gather rides memory")
            if in_place:
                logits, caches = T.decode_step(
                    params, tokens, pos, KB.paged_caches(layout, pools,
                                                         tables), cfg, flags)
                return logits, KB.pools_of(caches)
            caches = KB.gather_caches(layout, pools, tables)
            logits, new_caches = T.decode_step(params, tokens, pos, caches,
                                               cfg, flags)
            pools = KB.scatter_caches(layout, pools, new_caches, tables, pos)
            return logits, pools

    return step


def decodes_in_place(layout: "KB.PagedLayout", mesh=None) -> bool:
    """Whether :func:`make_paged_decode_step` reads the pools in place:
    every leaf is paged, unwindowed attention history, and no mesh."""
    return mesh is None and layout.in_place
