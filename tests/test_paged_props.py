"""Property tests (tier-2): the paged block-cache decode is *bit-identical*
to the contiguous continuously-batched decode.

For randomized generation depths, block sizes, and physical block
permutations, running ``make_paged_decode_step`` over pools + block
tables produces exactly the same logits and greedy tokens, step for
step, as ``make_batched_decode_step`` over the grown contiguous caches —
the block indirection is pure data movement, never arithmetic.  Ring
(windowed) and recurrent-state leaves take the slot-state path; a
deterministic ring case (prompt longer than the window) and a pure
recurrent case (mamba) pin those down.

Runs under real ``hypothesis`` when installed, else under the vendored
deterministic fallback (``tests/_hypothesis_vendor.py``) — keep that
module's strategy surface in sync with what this file imports.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.runtime import kv_blocks as KB
from repro.runtime import serve as RS

pytestmark = pytest.mark.tier2

S = 12
N_SLOTS = 2
FLAGS = T.RunFlags(remat="none")

# (gen, block_size) with block_size | (S + gen) — the layout's contract
_GEOMS = ((4, 2), (4, 4), (4, 8), (4, 16), (2, 2), (2, 7), (2, 14),
          (6, 2), (6, 3), (6, 6), (6, 9), (6, 18))

# worst case needs n_slots * max_blocks = 2 * (18 // 2) = 18 distinct
# physical blocks; a 24-wide permutation covers every geometry
_PERM_WIDTH = 24

geom_st = st.sampled_from(_GEOMS)
arch_st = st.sampled_from(("qwen3-4b", "h2o-danube-3-4b"))
perm_st = st.permutations(list(range(_PERM_WIDTH)))

_CACHE = {}


def _setup(arch, prompt_len):
    """One prefill per (arch, prompt_len): params, last-token logits and
    the contiguous prefix caches for N_SLOTS requests."""
    key = (arch, prompt_len)
    if key not in _CACHE:
        cfg = get_reduced(arch)
        params = T.init_params(jax.random.key(0), cfg, FLAGS.param_dtype)
        prompts = jax.random.randint(jax.random.key(1),
                                     (N_SLOTS, prompt_len), 0,
                                     cfg.vocab_size)
        logits, caches = RS.make_prefill_step(cfg, FLAGS)(params, prompts)
        _CACHE[key] = (cfg, params, logits, caches)
    return _CACHE[key]


def _paged_state(cfg, caches, prompt_len, gen, bs, perm):
    """Write the prefill caches into block pools under a permuted
    physical block assignment; returns (layout, pools, tables)."""
    lay = KB.paged_layout(cfg, n_slots=N_SLOTS, prompt_len=prompt_len,
                          max_new_tokens=gen, block_size=bs,
                          dtype=FLAGS.cache_dtype)
    pools = KB.make_pools(lay)
    mb = lay.max_blocks
    # restrict the fixed-width permutation to the blocks this geometry
    # needs (order preserved => still a permutation), skip the null block
    order = [v for v in perm if v < N_SLOTS * mb]
    tables = KB.null_table(lay)
    n_prefix = -(-prompt_len // bs)
    for slot in range(N_SLOTS):
        blocks = [1 + v for v in order[slot * mb:(slot + 1) * mb]]
        tables[slot, :] = blocks
        pre = jax.tree.map(
            lambda sp, c: jnp.take(c, jnp.asarray([slot]), axis=sp.batch_ax),
            lay.specs, caches, is_leaf=KB._spec_is_leaf)
        pools = KB.write_prefix(lay, pools, pre, jnp.int32(slot),
                                jnp.asarray(blocks[:n_prefix], jnp.int32))
    return lay, pools, tables


def _assert_paged_equals_contiguous(arch, prompt_len, gen, bs, perm,
                                    donate=False):
    cfg, params, logits0, caches = _setup(arch, prompt_len)
    lay, pools, tables = _paged_state(cfg, caches, prompt_len, gen, bs, perm)
    paged_step = RS.make_paged_decode_step(cfg, FLAGS, lay)
    ref_step = RS.make_batched_decode_step(cfg, FLAGS)
    if donate:
        # the engine's binding: one compiled program, pools donated
        paged_step = jax.jit(paged_step, donate_argnums=(3,))
        ref_step = jax.jit(ref_step)
    ref_caches = RS.grow_caches(cfg, caches, prompt_len, gen)

    tok = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)[:, None]
    t_ref = t_pg = tok
    pos = jnp.full((N_SLOTS,), prompt_len, jnp.int32)
    for j in range(gen - 1):
        l_ref, ref_caches = ref_step(params, t_ref, pos, ref_caches)
        l_pg, pools = paged_step(params, t_pg, pos, pools,
                                 jnp.asarray(tables))
        np.testing.assert_array_equal(
            np.asarray(l_pg), np.asarray(l_ref),
            err_msg=f"step {j}: paged logits diverged "
                    f"(gen={gen} bs={bs} arch={arch})")
        t_ref = jnp.argmax(l_ref[:, -1], axis=-1).astype(jnp.int32)[:, None]
        t_pg = jnp.argmax(l_pg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        np.testing.assert_array_equal(np.asarray(t_pg), np.asarray(t_ref))
        pos = pos + 1


@settings(max_examples=5, deadline=None)
@given(arch=arch_st, geom=geom_st, perm=perm_st)
def test_paged_decode_is_bit_identical(arch, geom, perm):
    gen, bs = geom
    _assert_paged_equals_contiguous(arch, S, gen, bs, perm)


def test_ring_case_prompt_longer_than_window():
    # h2o-danube reduced window = 32 < prompt 36: the attention leaves are
    # rings, classified slot-state — the paged path must wrap identically
    _assert_paged_equals_contiguous("h2o-danube-3-4b", 36, 4, 8,
                                    list(range(_PERM_WIDTH)))


def test_recurrent_state_case_mamba():
    # no full-sequence history at all: everything rides the slot-state
    # gather/scatter (including the pool-dtype coercion)
    _assert_paged_equals_contiguous("falcon-mamba-7b", S, 4, 8,
                                    list(reversed(range(_PERM_WIDTH))))


def test_hybrid_case_jamba():
    # paged KV for the attention layer beside Mamba slot state in one
    # layout: the gather/scatter step carries both
    _assert_paged_equals_contiguous("jamba2-mini", S, 4, 8,
                                    list(range(_PERM_WIDTH)))


def test_in_place_step_crosses_a_block_boundary_with_donated_pools():
    # the prompt fills exactly three blocks of 4: the first decode opens
    # a fresh block and the fifth opens another, each written in place
    # into pools the compiled step consumes
    cfg = get_reduced("qwen3-4b")
    assert RS.decodes_in_place(KB.paged_layout(
        cfg, n_slots=N_SLOTS, prompt_len=S, max_new_tokens=8, block_size=4))
    _assert_paged_equals_contiguous("qwen3-4b", S, 8, 4,
                                    list(reversed(range(_PERM_WIDTH))),
                                    donate=True)
