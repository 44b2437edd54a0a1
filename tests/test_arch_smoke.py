"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
same-family config and runs forward/train/prefill/decode on CPU with shape
and finiteness checks.  (Full configs are exercised only via the dry-run.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_reduced, SHAPES, \
    shape_applicable
from repro.models import transformer as T

FLAGS = T.RunFlags(remat="none")


@pytest.fixture(scope="module", params=ARCH_NAMES)
def arch_setup(request):
    cfg = get_reduced(request.param)
    params = T.init_params(jax.random.key(0), cfg)
    return request.param, cfg, params


def test_full_config_matches_assignment():
    expect = {
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "jamba2-mini": (32, 4096, 32, 8, 14336, 65536),
    }
    for name, (L, d, H, K, ff, V) in expect.items():
        c = get_config(name)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, d, H, K, ff, V), name


def test_moe_configs():
    assert get_config("dbrx-132b").moe.n_experts == 16
    assert get_config("dbrx-132b").moe.top_k == 4
    assert get_config("llama4-maverick-400b-a17b").moe.n_experts == 128
    assert get_config("llama4-maverick-400b-a17b").moe.top_k == 1
    assert get_config("falcon-mamba-7b").ssm.state_dim == 16
    jamba = get_config("jamba2-mini")
    assert (jamba.moe.n_experts, jamba.moe.top_k) == (16, 2)
    assert not jamba.moe.normalize_gates and not jamba.rope
    assert jamba.ssm.inner_norms and jamba.ssm.dt_rank == 256


def test_jamba_layer_order_is_transformers(monkeypatch):
    """The layer kinds and the expert layers of the full Jamba2-Mini are
    those ``transformers``' ``JambaConfig`` derives from the published
    periods and offsets."""
    monkeypatch.setenv("USE_TF", "0")
    tr = pytest.importorskip("transformers")
    cfg = get_config("jamba2-mini")
    ref = tr.JambaConfig(num_hidden_layers=cfg.n_layers, attn_layer_period=8,
                         attn_layer_offset=4, expert_layer_period=2,
                         expert_layer_offset=1, num_experts=16)
    kinds = ["attention" if k == "attn" else k for k in cfg.block_kinds()]
    assert kinds == ref.layers_block_type
    plen = len(cfg.pattern)
    assert [16 if cfg.moe_pattern[i % plen] else 1
            for i in range(cfg.n_layers)] == ref.layers_num_experts


def test_param_counts_in_expected_range():
    # sanity: derived parameter counts near the advertised sizes
    ranges = {
        "smollm-135m": (0.1e9, 0.2e9),
        "olmo-1b": (0.9e9, 1.6e9),
        "qwen3-4b": (3e9, 5.5e9),
        "falcon-mamba-7b": (5e9, 9e9),
        "qwen2-vl-72b": (60e9, 85e9),
        "dbrx-132b": (110e9, 150e9),
        "llama4-maverick-400b-a17b": (330e9, 460e9),
        "jamba2-mini": (50e9, 54e9),
    }
    for name, (lo, hi) in ranges.items():
        n = get_config(name).param_count()
        assert lo < n < hi, (name, n)
    # active params for MoE archs are far below total
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.active_param_count() < 0.15 * l4.param_count()
    # Jamba2-Mini: "52B-A12B"; every Mamba layer has its feed-forward
    assert 11.5e9 < get_config("jamba2-mini").active_param_count() < 12.5e9


def test_long500k_applicability():
    runnable = {a for a in ARCH_NAMES
                if shape_applicable(get_config(a), SHAPES["long_500k"])}
    assert runnable == {"h2o-danube-3-4b", "recurrentgemma-9b",
                        "falcon-mamba-7b"}


def test_train_step_shapes_and_finite(arch_setup):
    name, cfg, params = arch_setup
    B, S = 2, 64
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    loss = jax.jit(lambda p, b: T.forward_train(p, b, cfg, FLAGS))(
        params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), name


def test_train_grads_finite(arch_setup):
    name, cfg, params = arch_setup
    B, S = 2, 32
    toks = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    grads = jax.jit(jax.grad(
        lambda p: T.forward_train(p, batch, cfg, FLAGS)))(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g))), (name, path)


def test_prefill_and_decode(arch_setup):
    name, cfg, params = arch_setup
    B, S = 2, 32
    toks = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    logits, caches = jax.jit(
        lambda p, t: T.prefill(p, t, cfg, FLAGS))(params, toks)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), name

    cache = T.make_cache(cfg, B, S)
    logits2, cache2 = jax.jit(
        lambda p, t, c: T.decode_step(p, t, jnp.int32(S - 1), c, cfg,
                                      FLAGS))(params, toks[:, :1], cache)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2))), name
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_decode_cache_sizes_respect_window():
    cfg = get_reduced("h2o-danube-3-4b")
    cache = T.make_cache(cfg, 2, 1024)  # window = 32 in the reduced config
    leaves = jax.tree.leaves(cache)
    kv = [l for l in leaves if l.ndim == 5]
    assert kv and all(l.shape[2] == cfg.local_window for l in kv)
