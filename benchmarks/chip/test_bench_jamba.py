"""The Jamba cell at a small size on the CPU: the plain reference against
``transformers``' Jamba on the same weights; the program against the
reference, sublayer by sublayer, in prefill and in ``ServeEngine``'s
prefill and greedy decode (with the Mamba conv tails dropped between the
two as a fault).  ``test_bench_jamba_faults.py`` runs the whole tiny cell
on four devices.

The program runs with float32 compute where it is compared directly.  Its
Mamba mixer and MoE then agree with the reference's to float32 rounding,
but its attention casts q, k, v and the probabilities to bfloat16
(``models/attention.py``), and a random Jamba stack carries such a
rounding forward with a gain of some tens: the Mamba mixers' outputs are
several times the size of the residual stream (PERF.md, section 4).  So
the whole stack is held to ``TOL`` of the largest logit (it reads up to
0.06 over one period), and each fault reads many times that.
"""

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, weights

CELL = "jamba2-mini.prefill-ep4"
SEED = 2 ** 32 + 21
# the published kinds, periods, state, conv and expansion; small widths
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, vocab_size=256, num_experts=8,
            mamba_dt_rank=8)
TOL = 0.15


def tiny_cell(periods: int = 1) -> harness.Cell:
    """The cell with a small model (``periods`` periods of 8 layers from
    the cut's first layer) and a short mix."""
    cell = harness.load_cell(CELL)
    conf = copy.deepcopy(cell.conf)
    conf.update(TINY, num_hidden_layers=8 * periods)
    mix = dict(cell.mix, batch=2, prompt_tokens=32, batches=4,
               trace_seconds=0.3)
    checks = dict(cell.checks, steps=2, steps_from=2)
    return dataclasses.replace(cell, conf=conf, mix=mix, checks=checks)


def _weights(cell, seed=SEED):
    """(canonical weights, the program's) from one compiled draw."""
    adapter = cell.adapter()
    return weights.generator(
        cell.reference().weight_specs(cell.conf),
        adapt=lambda c: (c, adapter.program_params(c, cell.conf)))(seed)


def _flags(**kw):
    from repro.models import transformer as T
    return T.RunFlags(compute_dtype=jnp.float32, param_dtype=jnp.bfloat16,
                      cache_dtype=jnp.float32, remat="none", ssm_chunk=8,
                      **kw)


_REF = {}


def _ref_logits(cell, canon, tokens):
    """The reference's logits at every position (one compile per model)."""
    key = json.dumps(cell.conf, sort_keys=True)
    if key not in _REF:
        ref, conf = cell.reference(), cell.conf
        _REF[key] = jax.jit(lambda w, t: ref.logits(
            w, ref.forward(w, t, conf)[0], conf))
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF[key](canon, jnp.asarray(tokens, jnp.int32)))


@pytest.fixture(scope="module")
def tiny():
    """One period of the tiny cell: (cell, its ArchConfig, the canonical
    weights, the program's)."""
    cell = tiny_cell()
    return (cell, cell.adapter().arch_config(cell.conf)) + _weights(cell)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_reference_matches_transformers(monkeypatch):
    """Two whole periods from layer 0 (attention at layers 4 and 12, experts
    on odd layers), four experts: the reference's logits at every position
    are ``JambaForCausalLM``'s (slow Mamba path, eager attention) on the
    same float32 weights."""
    monkeypatch.setenv("USE_TF", "0")       # the torch model alone
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    cell = tiny_cell(periods=2)
    conf = dict(cell.conf, first_layer=0, num_experts=4)
    cell = dataclasses.replace(cell, conf=conf)
    ref = cell.reference()
    m = ref.dims(conf)
    canon = weights.generator(ref.weight_specs(conf), jnp.float32)(SEED)
    hf = tr.JambaConfig(
        vocab_size=m["V"], hidden_size=m["d"], intermediate_size=m["ff"],
        num_hidden_layers=m["L"], num_attention_heads=m["H"],
        num_key_value_heads=m["K"], rms_norm_eps=conf["rms_norm_eps"],
        tie_word_embeddings=False, num_experts=4, num_experts_per_tok=2,
        expert_layer_period=2, expert_layer_offset=1, attn_layer_period=8,
        attn_layer_offset=4, use_mamba_kernels=False, mamba_d_state=m["N"],
        mamba_d_conv=m["conv"], mamba_expand=conf["mamba_expand"],
        mamba_dt_rank=m["dtr"], mamba_conv_bias=True, mamba_proj_bias=False,
        attn_implementation="eager")
    model = tr.JambaForCausalLM(hf).eval()

    def t(a):
        return torch.from_numpy(np.array(a, np.float32))

    lw, seen = canon["layers"], {}
    sd = {"model.embed_tokens.weight": t(canon["embed"]),
          "model.final_layernorm.weight": t(canon["final_norm"]),
          "lm_head.weight": t(canon["unembed"])}
    for i, (mix, ffn) in enumerate(m["kinds"]):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(lw["ln1"][i])
        sd[p + "pre_ff_layernorm.weight"] = t(lw["ln2"][i])
        j = seen.get(mix, 0)
        if mix == "attention":
            for n in "qkvo":
                sd[p + f"self_attn.{n}_proj.weight"] = t(lw[f"attn_w{n}"][j]).T
        else:
            q = p + "mamba."
            sd[q + "in_proj.weight"] = t(lw["mamba_in_proj"][j]).T
            sd[q + "conv1d.weight"] = t(lw["mamba_conv_w"][j])[:, None]
            sd[q + "conv1d.bias"] = t(lw["mamba_conv_b"][j])
            sd[q + "x_proj.weight"] = t(lw["mamba_x_proj"][j]).T
            sd[q + "dt_proj.weight"] = t(lw["mamba_dt_proj"][j]).T
            sd[q + "dt_proj.bias"] = t(lw["mamba_dt_bias"][j])
            sd[q + "A_log"] = t(lw["mamba_A_log"][j])
            sd[q + "D"] = t(lw["mamba_D"][j])
            sd[q + "out_proj.weight"] = t(lw["mamba_out_proj"][j]).T
            for n, c in (("dt", "dt"), ("b", "B"), ("c", "C")):
                sd[q + f"{n}_layernorm.weight"] = t(lw[f"mamba_{c}_norm"][j])
        k = seen.get(ffn, 0)
        if ffn == "moe":
            q = p + "feed_forward."
            sd[q + "router.weight"] = t(lw["moe_router"][k]).T
            for e in range(4):
                for n in ("gate", "up"):
                    sd[q + f"experts.{e}.{n}_proj.weight"] = t(
                        lw[f"moe_w_{n}"][k][e])
                sd[q + f"experts.{e}.down_proj.weight"] = t(
                    lw["moe_w_down"][k][e]).T
        else:
            for n in ("gate", "up", "down"):
                sd[p + f"feed_forward.{n}_proj.weight"] = t(
                    lw[f"mlp_w_{n}"][k]).T
        seen[mix], seen[ffn] = j + 1, k + 1
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    toks = np.random.default_rng(1).integers(0, m["V"], (2, 24))
    with torch.no_grad():
        want = model(torch.from_numpy(toks)).logits.numpy()
    got = _ref_logits(cell, canon, toks)
    assert _rel(got, want) < 1e-4


def test_adapter_config_is_the_registered_stage():
    """The cell's ``ArchConfig`` is ``configs/jamba2_mini.CONFIG``'s layers
    4-11 (its pattern read from layer 4), not a second source of shape."""
    from repro.configs import jamba2_mini
    cell = harness.load_cell(CELL)
    cfg = cell.adapter().arch_config(cell.conf)
    want = jamba2_mini.stage(cell.conf["first_layer"],
                             cell.conf["num_hidden_layers"])
    assert (want.pattern[0], want.n_layers) == ("attn", 8)
    assert cfg == dataclasses.replace(want, name=cell.conf["name"])


def test_program_tree_holds_each_layer_s_weights():
    """Two periods: the adapter's parameter tree is the program's, and
    pattern position ``b`` of group ``g`` holds the canonical weights of
    layer ``8 g + b`` of its kind (each canonical row marked with its
    index among the layers of its kind)."""
    from repro.models import transformer as T
    cell = tiny_cell(periods=2)
    cfg = cell.adapter().arch_config(cell.conf)
    specs = cell.reference().weight_specs(cell.conf)
    canon = {"layers": {}}
    for name, (shape, _, _) in specs.items():
        leaf = np.broadcast_to(np.arange(shape[0], dtype=np.float32).reshape(
            (-1,) + (1,) * (len(shape) - 1)), shape) \
            if name.startswith("layers/") else np.zeros(shape, np.float32)
        (canon["layers"] if name.startswith("layers/") else canon)[
            name.split("/")[-1]] = leaf
    params = cell.adapter().program_params(canon, cell.conf)
    want = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg,
                                                jnp.bfloat16))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape
    seen = {}
    for i, (mix, ffn) in enumerate(cell.reference().dims(cell.conf)["kinds"]):
        g, b = divmod(i, len(cfg.pattern))
        blk = jax.tree.map(lambda a: a[g], params["groups"][f"b{b}"])
        j, k = seen.get(mix, 0), seen.get(ffn, 0)
        assert np.all(blk["ln1"]["scale"] == i)
        assert all(np.all(v == j) for v in blk["mixer"].values())
        assert all(np.all(v == k) for v in blk["ffn"].values())
        seen[mix], seen[ffn] = j + 1, k + 1


def test_each_sublayer_matches_the_reference(tiny):
    """At float32 compute the program's Mamba mixer (chunked scan, inner
    norms) and its MoE (raw top-2 gates, no capacity drop) agree with the
    reference's to float32 rounding; its attention without positions to a
    few bfloat16 steps (it rounds q, k, v and the probabilities)."""
    from repro.models import attention as A, moe as M, ssm as SSM
    cell, cfg, canon, params = tiny
    ref, conf = cell.reference(), cell.conf
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))

    def both(canon, params, x):
        lw = {k: v[0] for k, v in canon["layers"].items()}
        grp = jax.tree.map(lambda a: a[0], params["groups"])
        f32 = dict(compute_dtype=jnp.float32)
        return {"mamba": (SSM.mamba_apply(grp["b1"]["mixer"], x, cfg,
                                          chunk=8, **f32)[0],
                          ref.mamba(lw, x, conf, ref.highest)),
                "moe": (M.moe_apply(grp["b1"]["ffn"], x, cfg, mode="mem",
                                    model_axis=None, **f32)[0],
                        ref.moe(lw, x, 2, ref.highest)[0]),
                "attn": (A.attn_apply(grp["b0"]["mixer"], x, cfg, pos,
                                      impl="full", **f32),
                         ref.attention(lw, x, conf, ref.highest))}

    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out = jax.jit(both)(canon, params, x)
    assert _rel(*out["mamba"]) < 1e-5
    assert _rel(*out["moe"]) < 1e-5
    (got, (gk, _)), (want, (k, _)) = out["attn"]
    assert _rel(got, want) < 4 * 2.0 ** -8
    assert _rel(gk, k) < 1e-5


def test_prefill_matches_the_reference(tiny):
    from repro.models import transformer as T
    cell, cfg, canon, params = tiny
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 32))
    got, caches = T.prefill(params, jnp.asarray(toks, jnp.int32), cfg,
                            _flags(attn_impl="blockwise", attn_chunk=8))
    assert _rel(got[:, 0], _ref_logits(cell, canon, toks)[:, -1]) < TOL


@pytest.fixture(scope="module")
def engine_errors(tiny):
    """Prefill one prompt through ``ServeEngine`` (hybrid layout: paged KV
    for the attention layer, slot state for the Mamba layers, the
    gather/scatter step) and decode greedily, as it is and again with the
    Mamba layers' conv tails dropped between prefill and decode; for each,
    the relative error of each step's logits against the reference's full
    forward over the prompt and the tokens served before it."""
    from repro.runtime import kv_blocks as KB
    from repro.runtime.engine import ServeEngine
    cell, cfg, canon, params = tiny
    P, gen = 16, 8
    eng = ServeEngine(cfg, prompt_len=P, max_new_tokens=gen, n_slots=2,
                      block_size=8, params=params,
                      flags=_flags(attn_impl="full"))
    assert not eng._in_place
    seen, fault = [], []
    prefill, decode = eng._prefill, eng._decode

    def spy_prefill(p, toks):
        logits, caches = prefill(p, toks)
        seen.append(np.asarray(logits[0, -1]))
        if fault:
            caches = jax.tree_util.tree_map_with_path(
                lambda path, c: jnp.zeros_like(c)
                if "conv" in jax.tree_util.keystr(path) else c, caches)
        return logits, caches

    def spy_decode(p, toks, pos, pools, tables):
        logits, pools = decode(p, toks, pos, pools, tables)
        live = np.flatnonzero(np.asarray(tables)[:, 0] != KB.NULL_BLOCK)
        seen.append(np.asarray(logits[live[0], -1]))
        return logits, pools

    eng._prefill, eng._decode = spy_prefill, spy_decode
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, P)
    out = {}
    for name in (None, "conv_tail_dropped"):
        fault[:] = [name] if name else []
        seen.clear()
        req = eng.submit(prompt, gen)
        while eng.pending or eng.n_active:
            eng.step()
        assert len(seen) == gen
        want = _ref_logits(cell, canon, np.asarray([list(prompt) +
                                                    req.generated]))[0]
        out[name] = [_rel(g, want[P - 1 + i]) for i, g in enumerate(seen)]
    assert eng.trace_counts == {"prefill": 1, "decode": 1, "admit": 1}
    return out


@pytest.mark.parametrize("fault", [None, "conv_tail_dropped"])
def test_engine_prefill_and_decode_match_the_reference(engine_errors,
                                                       fault):
    errs = engine_errors[fault]
    assert errs[0] < TOL, errs                      # the prefill's logits
    if fault is None:
        assert max(errs) < TOL, errs
    else:
        assert errs[1] > 4 * TOL, errs              # the first decode step
