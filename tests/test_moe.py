"""MoE routing/dispatch semantics (single-device local path; the
distributed mem-vs-mcast equivalence runs in test_distributed.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.models import moe as M


def _setup(arch="dbrx-132b", seed=0):
    cfg = get_reduced(arch)
    params = M.moe_init(jax.random.key(seed), cfg)
    return cfg, params


def _dense_oracle(params, x, cfg):
    """Every token through its top-k experts at unlimited capacity."""
    B, S, d = x.shape
    x_flat = x.reshape(-1, d)
    gates, idx, _ = M._route(params["router"], x_flat, cfg.moe.top_k)
    out = np.zeros((B * S, d), np.float32)
    for e in range(cfg.moe.n_experts):
        toks = np.asarray(x_flat, np.float32)
        g = jnp.einsum("cd,df->cf", x_flat.astype(jnp.bfloat16),
                       params["w_gate"][e].astype(jnp.bfloat16))
        u = jnp.einsum("cd,df->cf", x_flat.astype(jnp.bfloat16),
                       params["w_up"][e].astype(jnp.bfloat16))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(jnp.bfloat16) * u
        y_e = jnp.einsum("cf,fd->cd", h,
                         params["w_down"][e].astype(jnp.bfloat16))
        w_e = np.asarray(jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1))
        out += np.asarray(y_e, np.float32) * w_e[:, None]
    return out.reshape(B, S, d)


def test_moe_local_matches_dense_oracle():
    cfg, params = _setup()
    # capacity_factor high enough that nothing drops
    cfg = cfg.__class__(**{**cfg.__dict__,
                           "moe": cfg.moe.__class__(cfg.moe.n_experts,
                                                    cfg.moe.top_k, 8.0)})
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y, aux = M.moe_apply(params, x, cfg, mode="mem", model_axis=None)
    oracle = _dense_oracle(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), oracle, rtol=5e-2, atol=5e-2)
    assert np.isfinite(float(aux))


def test_router_topk_normalized():
    cfg, params = _setup()
    x = jax.random.normal(jax.random.key(2), (32, cfg.d_model))
    gates, idx, aux = M._route(params["router"], x, cfg.moe.top_k)
    assert gates.shape == (32, cfg.moe.top_k)
    np.testing.assert_allclose(jnp.sum(gates, -1), jnp.ones(32), rtol=1e-5)
    # top-k indices are distinct per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == cfg.moe.top_k


def test_router_keeps_raw_softmax_gates_without_normalize():
    """Jamba's gates: the top-k softmax probabilities themselves, the same
    experts as the normalized route, summing to less than 1."""
    cfg, params = _setup()
    x = jax.random.normal(jax.random.key(2), (32, cfg.d_model))
    g_n, idx_n, _ = M._route(params["router"], x, cfg.moe.top_k)
    g_r, idx_r, _ = M._route(params["router"], x, cfg.moe.top_k,
                             normalize=False)
    np.testing.assert_array_equal(idx_r, idx_n)
    probs = jax.nn.softmax(x @ params["router"].astype(jnp.float32), -1)
    np.testing.assert_allclose(
        g_r, jnp.take_along_axis(probs, idx_r, -1), rtol=1e-5)
    assert bool(jnp.all(jnp.sum(g_r, -1) < 1.0))
    np.testing.assert_allclose(g_n, g_r / jnp.sum(g_r, -1, keepdims=True),
                               rtol=1e-5)


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 100))
def test_capacity_drops_lowest_gates(seed):
    """When an expert is oversubscribed, the kept tokens are the
    highest-gate ones (the documented drop policy)."""
    cfg, params = _setup(seed=seed)
    x = jax.random.normal(jax.random.key(seed), (1, 8, cfg.d_model))
    x_flat = x.reshape(-1, cfg.d_model)
    gates, idx, _ = M._route(params["router"], x_flat, cfg.moe.top_k)
    experts = jnp.arange(cfg.moe.n_experts)
    toks, src, w = M._select_for_experts(x_flat, gates, idx, experts, 2)
    w = np.asarray(w)
    for e in range(cfg.moe.n_experts):
        g = np.asarray(jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1))
        kept = w[e][w[e] > 0]
        expected = np.sort(g[g > 0])[::-1][:2]
        np.testing.assert_allclose(np.sort(kept)[::-1], expected, rtol=1e-5)


def test_top1_is_unicast_top4_is_multicast():
    """The user-field analogy: top-1 routes each token to exactly one
    expert (unicast P2P), top-k to k (multicast)."""
    for arch, k in (("llama4-maverick-400b-a17b", 1), ("dbrx-132b", 2)):
        cfg, params = _setup(arch)
        x = jax.random.normal(jax.random.key(3), (16, cfg.d_model))
        gates, idx, _ = M._route(params["router"], x, cfg.moe.top_k)
        assert idx.shape[-1] == k == cfg.moe.top_k
