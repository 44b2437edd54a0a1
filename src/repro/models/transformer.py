"""Arch-config-driven decoder stack.

Blocks are grouped by the config's repeating ``pattern`` and scanned with
``jax.lax.scan`` over stacked parameters (keeps HLO size O(1) in depth, which
matters for 64-80 layer dry-runs).  Remainder layers (pattern not dividing
n_layers, e.g. RecurrentGemma's 38 = 12*3 + 2) run unscanned.

Three entry points: ``forward_train`` (loss), ``prefill`` (cache build +
last-position logits), ``decode_step`` (one token through the cache).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import CommMode
from repro.core.sharding import (current_comm_plan, current_mesh,
                                 logical_to_pspec)
from repro.core.socket import mem_write, socket_for_axis
from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import griffin as G


@dataclasses.dataclass(frozen=True)
class RunFlags:
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    opt_dtype: Any = jnp.float32     # bf16 halves optimizer memory (400B fit)
    cache_dtype: Any = jnp.bfloat16
    remat: str = "full"          # "none" | "full"
    moe_mode: str = "mem"        # "mem" | "mcast"  (paper comm modes)
    distributed: bool = False    # use shard_map for MoE dispatch
    attn_impl: str = "flash"     # "flash" (custom-vjp blockwise) | "full" | "blockwise"
    attn_chunk: int = 512
    ssm_chunk: int = 128
    ce_chunk: int = 512
    aux_loss_coef: float = 0.01
    # route the dense-MLP blocks through the socket's fused-matmul issue
    # sites (shard_map over the model axis; see models.layers.mlp_apply_tp)
    ffn_tp: bool = False
    # dispatch the Pallas comm kernels (multicast stream, FUSED_RING) at
    # socket sites that qualify; kernel_interpret forwards interpret-mode
    # params on CPU (tests pass interpret_params())
    use_comm_kernels: bool = False
    kernel_interpret: Any = None


# ------------------------------------------------------------- block defs ----

def _ffn_kind(cfg: ArchConfig, kind: str, pos: int = 0) -> Optional[str]:
    """pos = position within the repeating pattern (llama4 and Jamba
    interleave dense and MoE FFNs via cfg.moe_pattern).  Every kind of
    block takes one; none where the arch has no FFN width (Falcon-Mamba)."""
    if cfg.d_ff == 0 and cfg.dense_ff == 0:
        return None
    if cfg.moe is not None and (cfg.moe_pattern is None or
                                cfg.moe_pattern[pos % len(cfg.pattern)]):
        return "moe"
    return "mlp"


def block_init(key, cfg: ArchConfig, kind: str, dtype, pos: int = 0):
    norm_init, _, _ = L.make_norm(cfg)
    ks = jax.random.split(key, 2)
    p: Dict[str, Any] = {"ln1": norm_init(cfg.d_model, dtype)}
    if kind in ("attn", "swa"):
        p["mixer"] = A.attn_init(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = SSM.mamba_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = G.rglru_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    fk = _ffn_kind(cfg, kind, pos)
    if fk:
        p["ln2"] = norm_init(cfg.d_model, dtype)
        ff = cfg.dense_ff or cfg.d_ff
        p["ffn"] = (M.moe_init(ks[1], cfg, dtype) if fk == "moe"
                    else L.mlp_init(ks[1], cfg.d_model, ff, dtype))
    return p


def block_axes(cfg: ArchConfig, kind: str, pos: int = 0):
    _, norm_axes, _ = L.make_norm(cfg)
    a: Dict[str, Any] = {"ln1": norm_axes()}
    if kind in ("attn", "swa"):
        a["mixer"] = A.attn_axes(cfg)
    elif kind == "mamba":
        a["mixer"] = SSM.mamba_axes(cfg)
    elif kind == "rglru":
        a["mixer"] = G.rglru_axes(cfg)
    fk = _ffn_kind(cfg, kind, pos)
    if fk:
        a["ln2"] = norm_axes()
        a["ffn"] = M.moe_axes(cfg) if fk == "moe" else L.mlp_axes()
    return a


def _bd_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _moe_ffn(params, h, cfg, flags: RunFlags):
    """MoE dispatch honouring the configured communication mode (C2/C4).

    An active :class:`CommPlan` (installed by ``use_rules(...,
    comm_plan=...)``, typically planner-built) overrides ``flags.moe_mode``:
    ``MEM`` keeps the shared-memory baseline; ``P2P``/``MCAST`` take the
    direct dispatch path (top-1 = unicast, the paper's degeneracy)."""
    mesh = current_mesh()
    if not flags.distributed or mesh is None or "model" not in mesh.axis_names:
        return M.moe_apply(params, h, cfg, mode="mem", model_axis=None,
                           compute_dtype=flags.compute_dtype)
    bd = _bd_axes(mesh)
    mode = flags.moe_mode
    plan = current_comm_plan()
    if plan is not None:
        mode = "mem" if plan.mode("moe_dispatch") is CommMode.MEM else "mcast"
    # the dispatch's sequence axis follows the ``seq_sp`` rule (the
    # ``moe_dispatch`` overlay in RULE_OVERLAYS rewrites it when the plan
    # picks the shared-memory baseline), not a hard-coded mesh axis
    seq_ax = logical_to_pspec(("seq_sp",), mesh=mesh)[0] \
        if mode == "mcast" else None
    x_spec = P(bd, seq_ax, None)
    param_specs = jax.tree.map(
        lambda names: logical_to_pspec(tuple(
            n if n == "experts" else None for n in names), mesh=mesh),
        M.moe_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))

    def body(p, x):
        y, aux = M.moe_apply(p, x, cfg, mode=mode, model_axis="model",
                             compute_dtype=flags.compute_dtype,
                             use_kernels=flags.use_comm_kernels,
                             interpret=flags.kernel_interpret)
        for ax in mesh.axis_names:
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    fn = jax.shard_map(body, mesh=mesh, in_specs=(param_specs, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    y, aux = fn(params, h)
    y = mem_write(y, "moe_output", ("batch", "seq", "embed"))
    return y, aux


def _mlp_ffn_tp(params, h, flags: RunFlags):
    """Dense-MLP block routed through the socket's fused-matmul issue
    sites: shard_map over the model axis, sequence-parallel activations,
    weights column/row-sharded — the up/gate gather and the down
    projection's matmul+reduce-scatter issue as fused transfers (the
    FUSED_RING kernels under ``use_comm_kernels``, the lax paths
    otherwise; identical numbers either way).  Falls back to the GSPMD
    ``mlp_apply`` when no model axis is live or the shapes do not divide
    the ring."""
    mesh = current_mesh()
    if not flags.distributed or mesh is None or \
            "model" not in mesh.axis_names:
        return L.mlp_apply(params, h, compute_dtype=flags.compute_dtype)
    M = mesh.shape["model"]
    B, S, _ = h.shape
    ff = params["w_gate"].shape[-1]
    bd = _bd_axes(mesh)
    bd_size = 1
    for a in bd:
        bd_size *= mesh.shape[a]
    if M < 2 or ff % M or B % max(bd_size, 1) or S % M:
        # sequence-parallel activations and column/row weight shards must
        # divide the mesh axes evenly for the shard_map specs
        return L.mlp_apply(params, h, compute_dtype=flags.compute_dtype)
    x_spec = P(bd, "model", None)
    param_specs = {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                   "w_down": P("model", None)}

    def body(p, x):
        Bl, Sl, d = x.shape
        sock = socket_for_axis("model",
                               use_kernels=flags.use_comm_kernels,
                               interpret=flags.kernel_interpret)
        y = L.mlp_apply_tp(p, x.reshape(Bl * Sl, d), socket=sock,
                           compute_dtype=flags.compute_dtype)
        return y.reshape(Bl, Sl, d)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(param_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    y = fn({k: params[k] for k in ("w_gate", "w_up", "w_down")}, h)
    return mem_write(y, "mlp_output", ("batch", "seq", "embed"))


def block_apply(params, x, cfg: ArchConfig, kind: str, flags: RunFlags,
                pos, cache=None, decode: bool = False, pat_pos: int = 0):
    """Returns (x_out, new_cache, aux_loss)."""
    _, _, norm = L.make_norm(cfg)
    aux = jnp.zeros((), jnp.float32)
    h = norm(params["ln1"], x)

    window = cfg.local_window if kind == "swa" else cfg.sliding_window
    if kind in ("attn", "swa"):
        if decode and isinstance(cache, A.PagedKV):
            assert not window, "a windowed history is read as a ring"
            y, new_cache = A.paged_decode_attn_apply(
                params["mixer"], h, cfg, cache, pos,
                compute_dtype=flags.compute_dtype)
        elif decode:
            y, new_cache = A.decode_attn_apply(
                params["mixer"], h, cfg, cache, pos,
                compute_dtype=flags.compute_dtype, window=window)
        else:
            y, kv = A.attn_apply(params["mixer"], h, cfg, pos,
                                 chunk=flags.attn_chunk,
                                 compute_dtype=flags.compute_dtype,
                                 window=window, impl=flags.attn_impl)
            new_cache = {"k": kv[0].astype(flags.cache_dtype),
                         "v": kv[1].astype(flags.cache_dtype)}
    elif kind == "mamba":
        if decode:
            y, new_cache = SSM.mamba_decode_step(
                params["mixer"], h, cfg, cache, compute_dtype=flags.compute_dtype)
        else:
            y, new_cache = SSM.mamba_apply(
                params["mixer"], h, cfg, cache, chunk=flags.ssm_chunk,
                compute_dtype=flags.compute_dtype)
    elif kind == "rglru":
        if decode:
            y, new_cache = G.rglru_decode_step(
                params["mixer"], h, cfg, cache, compute_dtype=flags.compute_dtype)
        else:
            y, new_cache = G.rglru_apply(
                params["mixer"], h, cfg, cache, chunk=flags.ssm_chunk,
                compute_dtype=flags.compute_dtype)
    else:
        raise ValueError(kind)
    x = x + y

    fk = _ffn_kind(cfg, kind, pat_pos)
    if fk:
        h = norm(params["ln2"], x)
        if fk == "moe":
            y, aux = _moe_ffn(params["ffn"], h, cfg, flags)
        elif flags.ffn_tp:
            y = _mlp_ffn_tp(params["ffn"], h, flags)
        else:
            y = L.mlp_apply(params["ffn"], h, compute_dtype=flags.compute_dtype)
        x = x + y
    x = mem_write(x, "block_activation", ("batch", "seq", "embed"))
    return x, new_cache, aux


# --------------------------------------------------------------- full model ----

def _grouping(cfg: ArchConfig):
    kinds = cfg.block_kinds()
    plen = len(cfg.pattern)
    n_groups = len(kinds) // plen
    rem = kinds[n_groups * plen:]
    return cfg.pattern, n_groups, rem


def init_params(key, cfg: ArchConfig, dtype=jnp.float32):
    pattern, n_groups, rem = _grouping(cfg)
    norm_init, _, _ = L.make_norm(cfg)
    keys = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.embedding_init(keys[1], cfg.vocab_size,
                                             cfg.d_model, dtype)

    def group_init(k):
        ks = jax.random.split(k, len(pattern))
        return {f"b{i}": block_init(ks[i], cfg, kind, dtype, pos=i)
                for i, kind in enumerate(pattern)}

    if n_groups:
        gkeys = jax.random.split(keys[2], n_groups)
        params["groups"] = jax.vmap(group_init)(gkeys)
    if rem:
        rkeys = jax.random.split(keys[3], len(rem))
        params["rem"] = {f"r{i}": block_init(rkeys[i], cfg, kind, dtype,
                                             pos=i)
                         for i, kind in enumerate(rem)}
    return params


def init_params_on_device(key, cfg: ArchConfig, dtype=jnp.float32,
                          shardings=None):
    """:func:`init_params` as one compiled program.  Each leaf is drawn,
    scaled and cast inside the program, so no float32 copy of a stacked
    leaf outlives its cast; with ``shardings`` (a pytree of
    ``NamedSharding`` matching the params) every leaf is born on its
    shards instead of whole on the default device."""
    return jax.jit(functools.partial(init_params, cfg=cfg, dtype=dtype),
                   out_shardings=shardings)(key)


def param_axes(cfg: ArchConfig):
    pattern, n_groups, rem = _grouping(cfg)
    _, norm_axes, _ = L.make_norm(cfg)
    axes: Dict[str, Any] = {
        "embed": L.embedding_axes(),
        "final_norm": norm_axes(),
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = L.embedding_axes()
    group = {f"b{i}": block_axes(cfg, kind, pos=i)
             for i, kind in enumerate(pattern)}
    if n_groups:
        axes["groups"] = jax.tree.map(
            lambda names: (None,) + names, group,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
    if rem:
        axes["rem"] = {f"r{i}": block_axes(cfg, kind, pos=i)
                       for i, kind in enumerate(rem)}
    return axes


def _apply_stack(params, x, cfg, flags, pos, caches, decode, collect_cache):
    """Runs grouped-scan + remainder blocks.  caches/new_caches mirror params
    structure under "groups"/"rem"."""
    pattern, n_groups, rem = _grouping(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    keep_cache = decode or collect_cache

    def group_body(x, gp, gc):
        aux_g = jnp.zeros((), jnp.float32)
        ncs = {}
        for i, kind in enumerate(pattern):
            c = gc[f"b{i}"] if gc is not None else None
            x, nc, aux = block_apply(gp[f"b{i}"], x, cfg, kind, flags, pos,
                                     cache=c, decode=decode, pat_pos=i)
            if keep_cache:
                ncs[f"b{i}"] = nc
            aux_g = aux_g + aux
        return x, ncs, aux_g

    body = group_body
    remat_on = flags.remat in ("full", "save_collectives") and not decode \
        and not collect_cache
    policy = None
    if flags.remat == "save_collectives":
        # keep post-all-reduce activations: the backward's recompute stays
        # local (no second pass over the ICI for the same partial sums)
        policy = jax.checkpoint_policies.save_only_these_names(
            "post_collective")
    if remat_on:
        body = (jax.checkpoint(group_body, policy=policy)
                if policy else jax.checkpoint(group_body))

    if n_groups:
        gcaches = caches.get("groups") if caches else None

        if gcaches is None and remat_on and n_groups >= 4:
            # sqrt(L) nested remat ("remat_scan"): an outer scan over
            # segments (checkpointed) of an inner scan over layers (each
            # layer checkpointed).  Saved residuals drop from O(L) full
            # activation stacks to O(sqrt(L)) + O(sqrt(L)) — the difference
            # between a 24 GB and a ~4 GB per-device remat stack at 36
            # layers x (256, 4096, 2560).
            g2 = max(1, int(round(n_groups ** 0.5)))
            while n_groups % g2:
                g2 -= 1
            g1 = n_groups // g2
            seg_params = jax.tree.map(
                lambda a: a.reshape((g1, g2) + a.shape[1:]), params["groups"])

            def layer_body(x, gp):
                x, ncs, aux_g = body(x, gp, None)
                return x, aux_g

            def seg_body(x, sp):
                return jax.lax.scan(layer_body, x, sp)

            seg_body = (jax.checkpoint(seg_body, policy=policy)
                        if policy else jax.checkpoint(seg_body))
            x, g_aux = jax.lax.scan(seg_body, x, seg_params)
            g_new = {}
        elif gcaches is None:
            def scan_body(x, gp):
                x, ncs, aux_g = body(x, gp, None)
                return x, (ncs, aux_g)
            x, (g_new, g_aux) = jax.lax.scan(scan_body, x, params["groups"])
        else:
            # paged histories ride the carry, whole, and each layer reads
            # and writes them in place at its index; the rest is scanned
            paged = {n: c for n, c in gcaches.items()
                     if isinstance(c, A.PagedKV)}
            scanned = {n: c for n, c in gcaches.items() if n not in paged}

            def scan_body(carry, inp):
                x, pools = carry
                gp, gc, layer = inp
                gc = dict(gc, **{n: c._replace(layer=layer)
                                 for n, c in pools.items()})
                x, ncs, aux_g = body(x, gp, gc)
                pools = {n: ncs.pop(n)._replace(layer=None) for n in pools}
                return (x, pools), (ncs, aux_g)
            (x, paged), (g_new, g_aux) = jax.lax.scan(
                scan_body, (x, paged),
                (params["groups"], scanned, jnp.arange(n_groups)))
            g_new.update(paged)
        aux_total = aux_total + jnp.sum(g_aux)
        if keep_cache:
            new_caches["groups"] = g_new

    for i, kind in enumerate(rem):
        rp = params["rem"][f"r{i}"]
        rc = caches["rem"][f"r{i}"] if caches else None
        x, nc, aux = block_apply(rp, x, cfg, kind, flags, pos, cache=rc,
                                 decode=decode, pat_pos=i)
        aux_total = aux_total + aux
        if keep_cache:
            new_caches.setdefault("rem", {})[f"r{i}"] = nc
    return x, new_caches, aux_total


def _unembed_table(params, cfg):
    return params["embed"]["table"] if cfg.tie_embeddings else params["unembed"]["table"]


def forward_train(params, batch, cfg: ArchConfig, flags: RunFlags):
    """batch: {"tokens": (B,S) int32, "labels": (B,S) int32} -> scalar loss."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(params["embed"], tokens, flags.compute_dtype)
    x, _, aux = _apply_stack(params, x, cfg, flags, pos, None, decode=False,
                             collect_cache=False)
    _, _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    loss = L.chunked_ce_loss(_unembed_table(params, cfg), x, labels,
                             chunk=flags.ce_chunk,
                             compute_dtype=flags.compute_dtype)
    if cfg.moe is not None:
        loss = loss + flags.aux_loss_coef * aux
    return loss


def prefill(params, tokens, cfg: ArchConfig, flags: RunFlags):
    """tokens (B,S) -> (last-position logits (B,1,V), caches)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(params["embed"], tokens, flags.compute_dtype)
    x, caches, _ = _apply_stack(params, x, cfg, flags, pos, None, decode=False,
                                collect_cache=True)
    _, _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x[:, -1:])
    logits = L.decode_logits(_unembed_table(params, cfg), x,
                             flags.compute_dtype)
    return logits, caches


def decode_step(params, token, pos_scalar, caches, cfg: ArchConfig,
                flags: RunFlags):
    """token (B,1) int32, pos_scalar scalar int32 — or a (B,) int32 vector
    of per-row positions (continuous batching) -> (logits (B,1,V), caches).

    A block's cache is either its contiguous history or an
    :class:`attention.PagedKV` over the stacked block pools, which the
    step reads and writes in place (returned as an updated ``PagedKV``)."""
    B = token.shape[0]
    x = L.embed_tokens(params["embed"], token, flags.compute_dtype)
    x, new_caches, _ = _apply_stack(params, x, cfg, flags, pos_scalar, caches,
                                    decode=True, collect_cache=True)
    _, _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.decode_logits(_unembed_table(params, cfg), x,
                             flags.compute_dtype)
    return logits, new_caches


# ------------------------------------------------------------ cache layout ----

def _block_cache_spec(cfg: ArchConfig, kind: str, B: int, skv: int, dtype):
    """(shape/dtype, logical-axes) spec tree for one block's decode cache."""
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    if kind in ("attn", "swa"):
        window = cfg.local_window if kind == "swa" else cfg.sliding_window
        s = min(skv, window) if window else skv
        sh = (B, s, K, hd)
        names = ("batch", "kv_seq", "kv_heads", "head_dim")
        return {"k": (sh, dtype, names), "v": (sh, dtype, names)}
    if kind == "mamba":
        di = cfg.ssm.expand * cfg.d_model
        return {"h": ((B, di, cfg.ssm.state_dim), jnp.float32,
                      ("batch", "state", None)),
                "conv": ((B, cfg.ssm.conv_dim - 1, di), jnp.float32,
                         ("batch", None, "state"))}
    if kind == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        return {"h": ((B, w), jnp.float32, ("batch", "state")),
                "conv": ((B, cfg.rglru.conv_dim - 1, w), jnp.float32,
                         ("batch", None, "state"))}
    raise ValueError(kind)


def cache_spec(cfg: ArchConfig, B: int, skv: int, dtype=jnp.bfloat16):
    """Returns a pytree of (shape, dtype, logical_names) leaves mirroring the
    decode-cache structure (leaves are 3-tuples, treated as leaves)."""
    pattern, n_groups, rem = _grouping(cfg)
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)
    out: Dict[str, Any] = {}
    if n_groups:
        group = {f"b{i}": _block_cache_spec(cfg, kind, B, skv, dtype)
                 for i, kind in enumerate(pattern)}
        out["groups"] = jax.tree.map(
            lambda sp: ((n_groups,) + sp[0], sp[1], (None,) + sp[2]),
            group, is_leaf=is_leaf)
    if rem:
        out["rem"] = {f"r{i}": _block_cache_spec(cfg, kind, B, skv, dtype)
                      for i, kind in enumerate(rem)}
    return out


def make_cache(cfg: ArchConfig, B: int, skv: int, dtype=jnp.bfloat16,
               as_specs: bool = False):
    spec = cache_spec(cfg, B, skv, dtype)
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)
    if as_specs:
        return jax.tree.map(lambda sp: jax.ShapeDtypeStruct(sp[0], sp[1]),
                            spec, is_leaf=is_leaf)
    return jax.tree.map(lambda sp: jnp.zeros(sp[0], sp[1]), spec,
                        is_leaf=is_leaf)


def cache_axes(cfg: ArchConfig, B: int = 1, skv: int = 1):
    spec = cache_spec(cfg, B, skv)
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)
    return jax.tree.map(lambda sp: sp[2], spec, is_leaf=is_leaf)
