"""Attention equivalences: flash == blockwise == full (values and grads),
RoPE/M-RoPE, and the prefill->decode == forward integration contract."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.models.attention import (flash_attention, blockwise_attention,
                                    full_attention, apply_rope)


def _qkv(key, B=2, S=128, K=2, G=2, hd=16, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, K, G, hd), dtype)
    k = jax.random.normal(k2, (B, S, K, hd), dtype)
    v = jax.random.normal(k3, (B, S, K, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("window", [0, 32])
@pytest.mark.parametrize("chunk", [32, 64])
def test_blockwise_matches_full(window, chunk):
    q, k, v = _qkv(jax.random.key(0))
    out_b = blockwise_attention(q, k, v, chunk=chunk, window=window)
    out_f = full_attention(q, k, v, window=window)
    np.testing.assert_allclose(out_b, out_f, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("window", [0, 32])
def test_flash_grads_match_full(window):
    q, k, v = _qkv(jax.random.key(1))
    g = jax.random.normal(jax.random.key(2), q.shape, q.dtype)
    f = lambda *a: jnp.sum(flash_attention(*a, 32, window) * g)
    r = lambda *a: jnp.sum(full_attention(*a, window=window) * g)
    np.testing.assert_allclose(flash_attention(q, k, v, 32, window),
                               full_attention(q, k, v, window=window),
                               rtol=2e-2, atol=2e-2)
    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(r, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=6e-2, atol=6e-2)


@pytest.mark.parametrize("shape", [(2, 64, 1, 4, 16),   # MQA
                                   (1, 64, 4, 1, 8),    # MHA
                                   (2, 128, 3, 3, 16)]) # GQA, odd heads
def test_attention_shape_sweep(shape):
    B, S, K, G, hd = shape
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(k1, (B, S, K, G, hd))
    k = jax.random.normal(k2, (B, S, K, hd))
    v = jax.random.normal(k3, (B, S, K, hd))
    out = blockwise_attention(q, k, v, chunk=32)
    np.testing.assert_allclose(out, full_attention(q, k, v),
                               rtol=2e-2, atol=2e-2)


def test_causality():
    """Changing future tokens must not affect past outputs."""
    q, k, v = _qkv(jax.random.key(4), S=64)
    out1 = full_attention(q, k, v)
    k2 = k.at[:, 48:].set(9.0)
    v2 = v.at[:, 48:].set(-9.0)
    out2 = full_attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :48], out2[:, :48], rtol=1e-5,
                               atol=1e-5)


def test_sliding_window_locality():
    """With window w, tokens further than w in the past are invisible."""
    q, k, v = _qkv(jax.random.key(5), S=128)
    w = 16
    out1 = full_attention(q, k, v, window=w)
    # perturb tokens 0..63; outputs at positions >= 64+w must not change
    k2 = k.at[:, :64].set(5.0)
    v2 = v.at[:, :64].set(5.0)
    out2 = full_attention(q, k2, v2, window=w)
    np.testing.assert_allclose(out1[:, 64 + w:], out2[:, 64 + w:],
                               rtol=1e-5, atol=1e-5)


def test_rope_orthogonal_and_relative():
    x = jax.random.normal(jax.random.key(6), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    y = apply_rope(x, pos, 10000.0)
    # norm preserving per pair
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.key(7), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.key(8), (1, 1, 1, 16))
    def dot_at(i, j):
        qi = apply_rope(q, jnp.asarray([[i]]), 10000.0)
        kj = apply_rope(k, jnp.asarray([[j]]), 10000.0)
        return float(jnp.sum(qi * kj))
    assert dot_at(3, 1) == pytest.approx(dot_at(10, 8), rel=1e-4)


def test_mrope_sections_differ_from_plain_rope():
    x = jax.random.normal(jax.random.key(9), (1, 8, 2, 16))
    pos3 = jnp.stack([jnp.arange(8), jnp.arange(8) * 2,
                      jnp.arange(8) * 3], axis=-1)[None]
    y_plain = apply_rope(x, jnp.arange(8)[None], 10000.0)
    y_m = apply_rope(x, pos3, 10000.0, mrope_sections=(4, 2, 2))
    assert not np.allclose(y_plain, y_m)
    # with identical components M-RoPE degrades to plain RoPE
    pos_same = jnp.broadcast_to(jnp.arange(8)[None, :, None], (1, 8, 3))
    y_same = apply_rope(x, pos_same, 10000.0, mrope_sections=(4, 2, 2))
    np.testing.assert_allclose(y_same, y_plain, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("arch", ["qwen3-4b", "h2o-danube-3-4b", "olmo-1b",
                                  "recurrentgemma-9b", "falcon-mamba-7b",
                                  "jamba2-mini"])
def test_prefill_then_decode_matches_forward(arch):
    """Integration contract: prefill(tokens[:-1]) + decode(tokens[-1])
    produces the same next-token logits as prefill(tokens)."""
    cfg = get_reduced(arch)
    flags = T.RunFlags(remat="none", attn_impl="full",
                       cache_dtype=jnp.float32)
    params = T.init_params(jax.random.key(0), cfg)
    B, S = 2, 32
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)

    logits_full, _ = T.prefill(params, toks, cfg, flags)

    _, caches = T.prefill(params, toks[:, :-1], cfg, flags)
    # grow attention caches from S-1 to S slots (decode appends in place)
    def grow(leaf):
        if leaf.ndim >= 3 and leaf.shape[-3] == S - 1:  # (.., B, skv, K, hd)
            pad = [(0, 0)] * leaf.ndim
            pad[-3] = (0, 1)
            return jnp.pad(leaf, pad)
        return leaf
    caches = jax.tree.map(grow, caches)
    logits_dec, _ = T.decode_step(params, toks[:, -1:],
                                  jnp.int32(S - 1), caches, cfg, flags)
    np.testing.assert_allclose(logits_dec, logits_full, rtol=3e-2, atol=3e-2)


def test_no_rope_projects_q_and_k_the_same_at_every_position():
    """``cfg.rope`` off (Jamba): q and k do not depend on the position, on
    the prefill path and the decode path alike; on, they do."""
    from repro.models.attention import _project_qkv, attn_init
    cfg = get_reduced("jamba2-mini")
    params = attn_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 1, cfg.d_model))
    for rope in (False, True):
        c = dataclasses.replace(cfg, rope=rope)
        q0, k0, _ = _project_qkv(params, x, c, jnp.zeros((2, 1), jnp.int32),
                                 jnp.float32)
        q9, k9, _ = _project_qkv(params, x, c, jnp.full((2, 1), 9),
                                 jnp.float32)
        same = np.allclose(q0, q9) and np.allclose(k0, k9)
        assert same is (not rope)
