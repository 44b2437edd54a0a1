"""SSM / RG-LRU recurrences vs. naive sequential oracles."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.models.ssm import (chunked_linear_scan, causal_conv1d,
                              mamba_apply, mamba_decode_step, mamba_init)
from repro.models.griffin import rglru_apply, rglru_decode_step, rglru_init


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 1000), chunk=st.sampled_from([1, 4, 8, 16]))
def test_chunked_linear_scan_matches_sequential(seed, chunk):
    key = jax.random.key(seed)
    B, S, W = 2, 16, 5
    a = jax.random.uniform(key, (B, S, W), minval=0.1, maxval=0.99)
    b = jax.random.normal(jax.random.key(seed + 1), (B, S, W))
    h0 = jax.random.normal(jax.random.key(seed + 2), (B, W))
    h_all, h_last = chunked_linear_scan(a, b, h0, chunk)
    # sequential oracle
    h = np.asarray(h0)
    for t in range(S):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        np.testing.assert_allclose(h_all[:, t], h, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_last, h, rtol=1e-5, atol=1e-5)


def test_causal_conv_matches_numpy():
    B, S, C, K = 2, 12, 3, 4
    x = jax.random.normal(jax.random.key(0), (B, S, C))
    w = jax.random.normal(jax.random.key(1), (C, K))
    state = jnp.zeros((B, K - 1, C))
    y, new_state = causal_conv1d(x, w, None, state)
    xp = np.concatenate([np.zeros((B, K - 1, C)), np.asarray(x)], axis=1)
    for t in range(S):
        expect = sum(xp[:, t + j] * np.asarray(w)[:, j] for j in range(K))
        np.testing.assert_allclose(y[:, t], expect, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new_state, xp[:, -K + 1:], rtol=1e-6)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_mamba_chunked_consistency(chunk):
    """The chunked scan must be invariant to chunk size."""
    cfg = get_reduced("falcon-mamba-7b")
    params = mamba_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model),
                          jnp.float32)
    y_ref, st_ref = mamba_apply(params, x, cfg, chunk=32)
    y, st = mamba_apply(params, x, cfg, chunk=chunk)
    np.testing.assert_allclose(y, y_ref, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(st["h"], st_ref["h"], rtol=2e-2, atol=2e-2)


def test_mamba_decode_matches_full():
    """Running tokens one at a time through decode must equal the full
    sequence pass (state-space consistency)."""
    cfg = get_reduced("falcon-mamba-7b")
    params = mamba_init(jax.random.key(0), cfg)
    B, S = 1, 8
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                          jnp.float32)
    y_full, _ = mamba_apply(params, x, cfg, chunk=8)
    state = None
    outs = []
    di = cfg.ssm.expand * cfg.d_model
    state = {"h": jnp.zeros((B, di, cfg.ssm.state_dim), jnp.float32),
             "conv": jnp.zeros((B, cfg.ssm.conv_dim - 1, di), jnp.float32)}
    for t in range(S):
        y_t, state = mamba_decode_step(params, x[:, t:t + 1], cfg, state)
        outs.append(y_t)
    y_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(y_dec, y_full, rtol=3e-2, atol=3e-2)


def test_rglru_decode_matches_full():
    cfg = get_reduced("recurrentgemma-9b")
    params = rglru_init(jax.random.key(0), cfg)
    B, S = 1, 8
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                          jnp.float32)
    y_full, _ = rglru_apply(params, x, cfg, chunk=8)
    w = cfg.rglru.lru_width or cfg.d_model
    state = {"h": jnp.zeros((B, w), jnp.float32),
             "conv": jnp.zeros((B, cfg.rglru.conv_dim - 1, w), jnp.float32)}
    outs = []
    for t in range(S):
        y_t, state = rglru_decode_step(params, x[:, t:t + 1], cfg, state)
        outs.append(y_t)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), y_full,
                               rtol=3e-2, atol=3e-2)


def test_rglru_decay_in_unit_interval():
    """RG-LRU stability: the decay a_t must stay in (0, 1)."""
    cfg = get_reduced("recurrentgemma-9b")
    params = rglru_init(jax.random.key(0), cfg)
    from repro.models.griffin import _gates_and_decay
    u = jax.random.normal(jax.random.key(2), (2, 16, cfg.rglru.lru_width))
    a, _ = _gates_and_decay(params, u, jnp.bfloat16)
    assert float(jnp.min(a)) > 0.0
    assert float(jnp.max(a)) < 1.0


def test_inner_norms_make_dt_b_c_scale_free():
    """Jamba's RMSNorms on dt, B and C after ``x_proj``: scaling ``x_proj``
    leaves the mixer's output as it was (up to the norms' eps); without
    the norms it does not."""
    cfg = get_reduced("jamba2-mini")
    params = mamba_init(jax.random.key(0), cfg)
    assert {"dt_norm", "B_norm", "C_norm"} <= set(params)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.float32)
    scaled = dict(params, x_proj=params["x_proj"] * 7.0)
    plain = dataclasses.replace(cfg, ssm=dataclasses.replace(
        cfg.ssm, inner_norms=False))

    @jax.jit
    def outputs(params, scaled, x):
        return [mamba_apply(p, x, c, chunk=8, compute_dtype=jnp.float32)[0]
                for c in (cfg, plain) for p in (scaled, params)]

    normed_s, normed, plain_s, plain_y = outputs(params, scaled, x)
    np.testing.assert_allclose(normed_s, normed, rtol=1e-4, atol=1e-5)
    assert not np.allclose(plain_s, plain_y, rtol=1e-2, atol=1e-2)


def test_jamba_mamba_decode_matches_full():
    """With the inner norms, token-at-a-time decode equals the full
    sequence pass."""
    cfg = get_reduced("jamba2-mini")
    params = mamba_init(jax.random.key(0), cfg)
    B, S = 1, 8
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                          jnp.float32)
    y_full, st_full = mamba_apply(params, x, cfg, chunk=4,
                                  compute_dtype=jnp.float32)
    di = cfg.ssm.expand * cfg.d_model
    state = {"h": jnp.zeros((B, di, cfg.ssm.state_dim), jnp.float32),
             "conv": jnp.zeros((B, cfg.ssm.conv_dim - 1, di), jnp.float32)}
    outs = []
    for t in range(S):
        y_t, state = mamba_decode_step(params, x[:, t:t + 1], cfg, state,
                                       compute_dtype=jnp.float32)
        outs.append(y_t)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), y_full,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state["h"], st_full["h"], rtol=1e-4,
                               atol=1e-5)
