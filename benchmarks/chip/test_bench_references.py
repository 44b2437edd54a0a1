"""The plain float32 references against the program, at a small size on the
CPU, on the benchmark's own seeded weights: Qwen3 prefill and paged-decode
logits, DBRX prefill logits through the MoE layer.

The program is run with float32 compute, so what is left between the two
is the program's attention, which casts q, k, v and the probabilities to
bfloat16 whatever the compute type (``models/attention.py``): the
tolerance is a few bfloat16 steps (2**-8 relative) of the largest logit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import testing, weights

TOL = 4 * 2.0 ** -8
# through the MoE the router reads inputs that the layer before rounded
# (bf16 attention), and top-k turns that rounding into larger changes; the
# MoE layer alone agrees to float32 rounding (3.5e-7 on these weights)
TOL_MOE = 8 * 2.0 ** -8


def _setup(cell_name, seed=2 ** 40 + 3):
    cell = testing.tiny_cell(cell_name)
    ref, adapter = cell.reference(), cell.adapter()
    cfg = adapter.arch_config(cell.conf)
    specs = ref.weight_specs(cell.conf)
    canon = weights.generator(specs)(seed)
    params = weights.generator(
        specs, adapt=lambda c: adapter.program_params(c, cell.conf))(seed)
    return cell, ref, cfg, canon, params


def _flags(**kw):
    from repro.models import transformer as T
    return T.RunFlags(compute_dtype=jnp.float32, param_dtype=jnp.bfloat16,
                      cache_dtype=jnp.float32, remat="none", **kw)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ref_logits(ref, conf, canon, tokens):
    with jax.default_matmul_precision("highest"):
        h, _ = ref.forward(canon, jnp.asarray(tokens), conf)
        return np.asarray(ref.logits(canon, h, conf))


def test_program_tree_matches_the_adapter():
    from repro.models import transformer as T
    for name in ("qwen3-4b.decode-saturated", "dbrx-132b.prefill-ep4"):
        cell, ref, cfg, canon, params = _setup(name)
        want = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg,
                                                    jnp.bfloat16))
        assert jax.tree.structure(params) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("prompt,impl", [(16, "full"), (32, "blockwise")])
def test_qwen3_prefill_logits(prompt, impl):
    from repro.models import transformer as T
    cell, ref, cfg, canon, params = _setup("qwen3-4b.decode-saturated")
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, prompt))
    flags = _flags(attn_impl=impl, attn_chunk=8)
    got, _ = T.prefill(params, jnp.asarray(toks, jnp.int32), cfg, flags)
    want = _ref_logits(ref, cell.conf, canon, toks)[:, -1:]
    assert _rel(got, want) < TOL


def test_qwen3_paged_decode_logits():
    """Prefill one prompt, land its cache in the block pools, then decode
    the reference's own greedy tokens through the paged step: every step's
    logits agree with the reference's full forward at that position."""
    from repro.models import transformer as T
    from repro.runtime import kv_blocks as KB
    from repro.runtime import serve as RS
    cell, ref, cfg, canon, params = _setup("qwen3-4b.decode-saturated")
    P, gen, bs = 16, 8, 8
    flags = _flags(attn_impl="full")
    layout = KB.paged_layout(cfg, n_slots=2, prompt_len=P,
                             max_new_tokens=gen, block_size=bs,
                             dtype=jnp.float32)
    alloc = KB.BlockAllocator(1 + layout.capacity_blocks)
    blocks = alloc.alloc(layout.max_blocks)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, P)
    _, caches = T.prefill(params, jnp.asarray(prompt[None], jnp.int32), cfg,
                          flags)
    pools = KB.write_prefix(layout, KB.make_pools(layout), caches,
                            jnp.int32(1), jnp.asarray(blocks, jnp.int32))
    tables = KB.null_table(layout)
    tables[1, :] = blocks
    step = jax.jit(RS.make_paged_decode_step(cfg, flags, layout))
    seq = list(prompt)
    for t in range(gen):
        want_all = _ref_logits(ref, cell.conf, canon,
                               np.asarray([seq + [0] * (P + gen - len(seq))]))
        nxt = int(np.argmax(want_all[0, len(seq) - 1]))
        tokens = np.zeros((2, 1), np.int32)
        tokens[1, 0] = nxt
        pos = np.asarray([0, len(seq)], np.int32)
        got, pools = step(params, jnp.asarray(tokens), jnp.asarray(pos),
                          pools, jnp.asarray(tables))
        seq.append(nxt)
        want = _ref_logits(ref, cell.conf, canon,
                           np.asarray([seq + [0] * (P + gen - len(seq))]))
        assert _rel(got[1, 0], want[0, len(seq) - 1]) < TOL, t


def test_dbrx_prefill_logits_through_the_moe():
    from repro.models import transformer as T
    cell, ref, cfg, canon, params = _setup("dbrx-132b.prefill-ep4")
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 32))
    got, _ = T.prefill(params, jnp.asarray(toks, jnp.int32), cfg,
                       _flags(attn_impl="blockwise", attn_chunk=16))
    want = _ref_logits(ref, cell.conf, canon, toks)[:, -1:]
    assert _rel(got, want) < TOL_MOE


def test_weights_stream_depends_on_seed_and_name_only():
    specs = {"a": ((3, 4), "normal", 1.0), "layers/b": ((2, 5), "scale", .1)}
    w1 = weights.generator(specs)(7)
    w2 = weights.generator(dict(specs, c=((2,), "normal", 1.0)))(7)
    w3 = weights.generator(specs)(2 ** 33 + 7)
    np.testing.assert_array_equal(np.asarray(w1["a"], np.float32),
                                  np.asarray(w2["a"], np.float32))
    assert not np.array_equal(np.asarray(w1["a"], np.float32),
                              np.asarray(w3["a"], np.float32))
    assert w1["layers"]["b"].shape == (2, 5)
    assert w1["a"].dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        weights.seed_words(-1)
    assert dataclasses.is_dataclass(testing.tiny_cell(
        "qwen3-4b.prefill-burst"))
