"""Paged (block) KV cache for the continuous-batching serving engine.

The contiguous serve path gives every request a private, monolithic
cache; under continuous batching that wastes a full ``S_max`` allocation
on every slot regardless of depth, and growing it is an O(S^2) repad.
This module stores the *full-sequence* attention leaves as pools of
fixed-size blocks instead:

* each paged leaf keeps a **pool** shaped ``(..., num_blocks, block_size,
  ...)`` — the per-request batch axis is replaced by a physical-block
  axis, the ``kv_seq`` axis by the block's slot count;
* a host-side :class:`BlockAllocator` hands out physical blocks from a
  free list (block 0 is the reserved *null block* backing inactive
  table entries);
* a per-slot **block table** ``(n_slots, max_blocks) int32`` maps each
  active request's logical block j to its physical block, and is passed
  to the decode step as a device array — growing a request is a host
  table write, never a retrace;
* where every leaf is paged, unwindowed attention history
  (:attr:`PagedLayout.in_place`), :func:`paged_caches` hands the decode
  step the whole pools as :class:`~repro.models.attention.PagedKV`: it
  writes each slot's new row in place and reads the live blocks through
  the table, with no contiguous view;
* otherwise :func:`gather_caches` materializes the contiguous per-slot
  view the model decode consumes (``jnp.take`` over the block axis) and
  :func:`scatter_caches` writes back only the single block containing
  each slot's write position.

Leaves that are *not* full-sequence attention history are *slot state*:
mamba/rglru recurrent state (fixed O(1) shape per request) and windowed
ring caches whose ring is no larger than the prompt (the contiguous
serve contract keeps those at ``S_prompt`` and wraps — a fixed-size
recurrent buffer in all but name).  Slot-state leaves live as dense
``(n_slots, ...)`` arrays: gather is identity, scatter is replacement.

Leaf classification keys on the **logical axis names** from
``transformer.cache_axes`` ("batch"/"kv_seq"), never on shape
coincidences — matching ``leaf.shape[-3] == S`` false-positives whenever
an unrelated cache dim equals the prompt length.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.models.attention import PagedKV


# ----------------------------------------------------------- block allocator ----

NULL_BLOCK = 0


class OutOfBlocksError(RuntimeError):
    """The pool has fewer free blocks than the allocation asked for."""


class BlockAllocator:
    """Host-side free-list allocator over ``num_blocks`` physical blocks.

    Block ``NULL_BLOCK`` (0) is reserved: it backs every inactive block-
    table entry and is never handed out.  Invariants (tier-1 tested):
    a block is never allocated twice without an intervening free; freeing
    a block not currently allocated (or the null block) raises."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (one is the reserved null)")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._used: set = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfBlocksError(
                f"asked for {n} blocks, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        return out

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("cannot free the reserved null block")
            if b not in self._used:
                raise ValueError(f"double free of block {b}")
            self._used.remove(b)
            self._free.append(b)


# ------------------------------------------------------------- cache layout ----

@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Geometry of one decode-cache leaf under the engine.

    ``names`` are the leaf's logical axes (group-scanned leaves carry a
    leading ``None``); ``contig_shape`` is the contiguous per-step view
    with ``n_slots`` at the batch axis; ``paged`` leaves additionally
    carry the block-pool geometry."""
    names: Tuple[Optional[str], ...]
    dtype: Any
    contig_shape: Tuple[int, ...]
    paged: bool
    skv: Optional[int] = None      # kv length of the contiguous view
    window: int = 0                # attention window a paged leaf masks

    @property
    def batch_ax(self) -> int:
        return self.names.index("batch")

    @property
    def kv_ax(self) -> int:
        return self.names.index("kv_seq")


def _spec_is_leaf(x) -> bool:
    return isinstance(x, LeafSpec)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of the engine's cache: which leaves are paged,
    block size, per-slot capacity.  ``specs`` mirrors the model's cache
    pytree structure with :class:`LeafSpec` leaves."""
    cfg: ArchConfig
    n_slots: int
    prompt_len: int
    max_new_tokens: int
    block_size: int
    specs: Any = dataclasses.field(hash=False, compare=False)

    @property
    def s_max(self) -> int:
        return self.prompt_len + self.max_new_tokens

    @property
    def max_blocks(self) -> int:
        return self.s_max // self.block_size

    @property
    def capacity_blocks(self) -> int:
        """Physical blocks needed to run every slot at full depth (the
        default pool provisioning), excluding the null block."""
        return self.n_slots * self.max_blocks

    @property
    def in_place(self) -> bool:
        """Every leaf is paged, unwindowed attention history inside the
        layer scan: decode can read and write the pools in place."""
        leaves = jax.tree.leaves(self.specs, is_leaf=_spec_is_leaf)
        return set(self.specs) == {"groups"} and all(
            sp.paged and not sp.window for sp in leaves)

    def blocks_needed(self, pos: int) -> int:
        """Blocks a request must own before writing position ``pos``."""
        return min(pos // self.block_size + 1, self.max_blocks)


def _leaf_specs_for_kind(cfg: ArchConfig, kind: str, n_slots: int,
                         prompt_len: int, s_max: int, dtype):
    """Per-leaf specs for one block kind, mirroring the *contiguous serve
    contract*: prefill emits full-``S_prompt`` attention prefixes; the
    serve driver grows them to ``S_prompt + GEN`` unless the leaf is a
    ring no larger than the prompt (``window <= S_prompt``), which stays
    at ``S_prompt`` and wraps.  Full-sequence leaves page; rings and
    recurrent state are slot state."""
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    if kind in ("attn", "swa"):
        window = cfg.local_window if kind == "swa" else cfg.sliding_window
        ring = bool(window) and prompt_len >= window
        skv = prompt_len if ring else s_max
        names = ("batch", "kv_seq", "kv_heads", "head_dim")
        spec = LeafSpec(names=names, dtype=dtype,
                        contig_shape=(n_slots, skv, K, hd),
                        paged=not ring, skv=skv, window=window or 0)
        return {"k": spec, "v": spec}
    if kind == "mamba":
        di = cfg.ssm.expand * cfg.d_model
        return {"h": LeafSpec(("batch", "state", None), jnp.float32,
                              (n_slots, di, cfg.ssm.state_dim), False),
                "conv": LeafSpec(("batch", None, "state"), jnp.float32,
                                 (n_slots, cfg.ssm.conv_dim - 1, di), False)}
    if kind == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        return {"h": LeafSpec(("batch", "state"), jnp.float32,
                              (n_slots, w), False),
                "conv": LeafSpec(("batch", None, "state"), jnp.float32,
                                 (n_slots, cfg.rglru.conv_dim - 1, w), False)}
    raise ValueError(kind)


def paged_layout(cfg: ArchConfig, *, n_slots: int, prompt_len: int,
                 max_new_tokens: int, block_size: int,
                 dtype=jnp.bfloat16) -> PagedLayout:
    s_max = prompt_len + max_new_tokens
    if s_max % block_size:
        raise ValueError(f"block_size {block_size} must divide "
                         f"prompt_len + max_new_tokens = {s_max}")
    pattern, n_groups, rem = T._grouping(cfg)
    specs: Dict[str, Any] = {}
    if n_groups:
        group = {f"b{i}": _leaf_specs_for_kind(cfg, kind, n_slots,
                                               prompt_len, s_max, dtype)
                 for i, kind in enumerate(pattern)}
        specs["groups"] = jax.tree.map(
            lambda sp: dataclasses.replace(
                sp, names=(None,) + sp.names,
                contig_shape=(n_groups,) + sp.contig_shape),
            group, is_leaf=_spec_is_leaf)
    if rem:
        specs["rem"] = {f"r{i}": _leaf_specs_for_kind(cfg, kind, n_slots,
                                                      prompt_len, s_max,
                                                      dtype)
                        for i, kind in enumerate(rem)}
    return PagedLayout(cfg=cfg, n_slots=n_slots, prompt_len=prompt_len,
                       max_new_tokens=max_new_tokens, block_size=block_size,
                       specs=specs)


def slot_state_bytes(layout: PagedLayout) -> int:
    """Bytes of one slot's row of every slot-state (not paged) leaf."""
    return sum(int(np.prod(sp.contig_shape)) // layout.n_slots *
               np.dtype(sp.dtype).itemsize
               for sp in jax.tree.leaves(layout.specs, is_leaf=_spec_is_leaf)
               if not sp.paged)


# ------------------------------------------------------------ pool storage ----

def _pool_shape(layout: PagedLayout, spec: LeafSpec) -> Tuple[int, ...]:
    if not spec.paged:
        return spec.contig_shape
    sh = list(spec.contig_shape)
    sh[spec.batch_ax] = 1 + layout.capacity_blocks   # + the null block
    sh[spec.kv_ax] = layout.block_size
    return tuple(sh)


def make_pools(layout: PagedLayout):
    """Zero-initialized device storage: block pools for paged leaves,
    dense slot-state arrays for the rest."""
    return jax.tree.map(
        lambda sp: jnp.zeros(_pool_shape(layout, sp), sp.dtype),
        layout.specs, is_leaf=_spec_is_leaf)


def pool_specs(layout: PagedLayout):
    """ShapeDtypeStructs of :func:`make_pools` (for eval_shape / jit)."""
    return jax.tree.map(
        lambda sp: jax.ShapeDtypeStruct(_pool_shape(layout, sp), sp.dtype),
        layout.specs, is_leaf=_spec_is_leaf)


# --------------------------------------------------------- gather / scatter ----

def _gather_leaf(layout: PagedLayout, spec: LeafSpec, pool, tables):
    """Pool -> contiguous per-slot view.  tables: (n_slots, max_blocks)
    int32 physical-block ids (null entries gather the zero block — the
    decode validity mask keeps them out of the softmax)."""
    if not spec.paged:
        return pool
    b, s = spec.batch_ax, spec.kv_ax
    pm = jnp.moveaxis(pool, (b, s), (0, 1))          # (blocks, bs, rest)
    flat = jnp.take(pm, tables.reshape(-1), axis=0)  # (slots*mb, bs, rest)
    n_slots, mb = tables.shape
    contig = flat.reshape((n_slots, mb * layout.block_size) + pm.shape[2:])
    return jnp.moveaxis(contig, (0, 1), (b, s))


def gather_caches(layout: PagedLayout, pools, tables):
    return jax.tree.map(
        lambda sp, pool: _gather_leaf(layout, sp, pool, tables),
        layout.specs, pools, is_leaf=_spec_is_leaf)


def _scatter_leaf(layout: PagedLayout, spec: LeafSpec, pool, new_contig,
                  tables, pos):
    """Write back the one block per slot containing the slot's write
    position.  Inactive slots (all-null tables) land on the null block —
    harmless garbage no active table references."""
    if not spec.paged:
        # keep the pool dtype stable: a decode step may hand back slot
        # state in its compute dtype, and a dtype flip would retrace
        return new_contig.astype(pool.dtype)
    b, s = spec.batch_ax, spec.kv_ax
    bs = layout.block_size
    pm = jnp.moveaxis(pool, (b, s), (0, 1))              # (blocks, bs, rest)
    cm = jnp.moveaxis(new_contig, (b, s), (0, 1))        # (slots, S, rest)
    n_slots, mb = tables.shape
    cm = cm.reshape((n_slots, mb, bs) + cm.shape[2:])
    # the decode write slot mirrors decode_attn_apply: pos mod capacity
    # (no-op below capacity; rings never page)
    j = jnp.mod(pos.astype(jnp.int32), mb * bs) // bs    # (n_slots,)
    blk = jax.vmap(lambda row, jj: jax.lax.dynamic_index_in_dim(
        row, jj, 0, keepdims=False))(cm, j)              # (slots, bs, rest)
    phys = jnp.take_along_axis(tables, j[:, None], axis=1)[:, 0]
    pm = pm.at[phys].set(blk.astype(pm.dtype))
    return jnp.moveaxis(pm, (0, 1), (b, s))


def scatter_caches(layout: PagedLayout, pools, new_caches, tables, pos):
    return jax.tree.map(
        lambda sp, pool, nc: _scatter_leaf(layout, sp, pool, nc, tables, pos),
        layout.specs, pools, new_caches, is_leaf=_spec_is_leaf)


def paged_caches(layout: PagedLayout, pools, tables):
    """The decode step's caches over :attr:`PagedLayout.in_place` pools:
    one :class:`PagedKV` per block of the layer scan, holding the whole
    stacked pools and the block table."""
    assert layout.in_place
    return {"groups": {n: PagedKV(leaf["k"], leaf["v"], tables)
                       for n, leaf in pools["groups"].items()}}


def pools_of(caches):
    """Inverse of :func:`paged_caches`: the pools a decode step returned."""
    return {"groups": {n: {"k": c.k, "v": c.v}
                       for n, c in caches["groups"].items()}}


def _write_prefix_leaf(layout: PagedLayout, spec: LeafSpec, pool,
                       prefix_leaf, slot, block_ids):
    """Admission: land one request's prefill cache.  ``prefix_leaf`` has
    batch 1 and (for attention) ``kv_seq == prompt_len``; paged leaves
    scatter it block-by-block into ``block_ids``, slot-state leaves write
    their row.  ``slot``/``block_ids`` are traced values — one trace
    serves every admission."""
    b = spec.batch_ax
    if not spec.paged:
        pm = jnp.moveaxis(pool, b, 0)
        row = jnp.moveaxis(prefix_leaf, b, 0)[0]
        return jnp.moveaxis(pm.at[slot].set(row.astype(pm.dtype)), 0, b)
    s = spec.kv_ax
    bs = layout.block_size
    n_pb = -(-layout.prompt_len // bs)                  # ceil
    pm = jnp.moveaxis(pool, (b, s), (0, 1))             # (blocks, bs, rest)
    cm = jnp.moveaxis(prefix_leaf, (b, s), (0, 1))[0]   # (S_prompt, rest)
    pad = n_pb * bs - layout.prompt_len
    if pad:
        cm = jnp.pad(cm, [(0, pad)] + [(0, 0)] * (cm.ndim - 1))
    cm = cm.reshape((n_pb, bs) + cm.shape[1:])
    pm = pm.at[block_ids[:n_pb]].set(cm.astype(pm.dtype))
    return jnp.moveaxis(pm, (0, 1), (b, s))


def write_prefix(layout: PagedLayout, pools, prefix_caches, slot, block_ids):
    """Write one admitted request's prefill caches into the pools.
    ``block_ids``: (>= ceil(prompt_len / block_size),) int32 physical
    blocks owned by the request, in logical order."""
    return jax.tree.map(
        lambda sp, pool, pre: _write_prefix_leaf(layout, sp, pool, pre,
                                                 slot, block_ids),
        layout.specs, pools, prefix_caches, is_leaf=_spec_is_leaf)


def null_table(layout: PagedLayout) -> np.ndarray:
    """Host block table with every entry on the null block."""
    return np.full((layout.n_slots, layout.max_blocks), NULL_BLOCK,
                   dtype=np.int32)
