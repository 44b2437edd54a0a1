"""Plain float32 reference of the DBRX mixture-of-experts decoder
(databricks/dbrx-base).

The attention, norms, rotary embeddings and output head are those of the
Qwen3 reference (``references/qwen3.py``) without qk-norm.  Each layer's
feed-forward is DBRX's MoE: a float32 router, softmax over the experts, the
top ``moe_top_k`` kept and divided by their sum (``transformers``' default
``moe_normalize_expert_weights`` of 1), and each chosen expert's SwiGLU
output weighted by its gate.  Written plainly: every expert runs over every
token and the gates, zero for experts not chosen, combine them.  Nothing is
dropped (DBRX is dropless).

Departures from the published model, the same as the configuration run
(``configs/dbrx-132b-ep4.json``): RMSNorm in place of DBRX's LayerNorm
without bias, and no ``clip_qkv`` (DBRX clamps q, k and v to [-8, 8]); the
program under test expresses neither.  Weights are random from a seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from references import qwen3

F32 = jnp.float32
highest = qwen3.highest


def _conf(conf):
    """The DBRX config's keys under the Qwen3 reference's names."""
    a, f = conf["attn_config"], conf["ffn_config"]
    return {
        "num_hidden_layers": conf["n_layers"],
        "hidden_size": conf["d_model"],
        "num_attention_heads": conf["n_heads"],
        "num_key_value_heads": a["kv_n_heads"],
        "head_dim": conf["d_model"] // conf["n_heads"],
        "intermediate_size": f["ffn_hidden_size"],
        "vocab_size": conf["vocab_size"],
        "rope_theta": a["rope_theta"],
        "rms_norm_eps": conf.get("norm_eps", 1e-6),
        "tie_word_embeddings": conf.get("tie_word_embeddings", False),
        "n_experts": f["moe_num_experts"],
        "top_k": f["moe_top_k"],
    }


def dims(conf):
    c = _conf(conf)
    out = qwen3.dims(c)
    out.update(E=c["n_experts"], top_k=c["top_k"])
    return out


def weight_specs(conf):
    m = dims(conf)
    L, d, E, ff = m["L"], m["d"], m["E"], m["ff"]
    dense_only = ("layers/q_norm", "layers/k_norm", "layers/w_gate",
                  "layers/w_up", "layers/w_down")
    specs = {k: v for k, v in qwen3.weight_specs(_conf(conf)).items()
             if k not in dense_only}
    specs.update({
        "layers/router": ((L, d, E), "normal", d ** -0.5),
        "layers/w_gate": ((L, E, d, ff), "normal", d ** -0.5),
        "layers/w_up": ((L, E, d, ff), "normal", d ** -0.5),
        "layers/w_down": ((L, E, ff, d), "normal", ff ** -0.5),
    })
    return specs


def moe(top_k):
    """The MoE feed-forward: (output, router margin of each token).  The
    margin is the gap between the ``top_k``-th largest router logit and
    the next: a rounding of the router's input by about that much changes
    the token's experts."""
    def ffn(lw, h, ein):
        B, S, d = h.shape
        x = h.reshape(B * S, d)
        logits = jnp.einsum("nd,de->ne", x, lw["router"],
                            precision=jax.lax.Precision.HIGHEST)
        top = jax.lax.top_k(logits, top_k + 1)[0]
        margin = (top[:, top_k - 1] - top[:, top_k]).reshape(B, S)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, top_k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        E = probs.shape[-1]
        combine = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) *
                          gates[..., None], axis=1)            # (N, E)
        g = ein("nd,edf->enf", x, lw["w_gate"])
        u = ein("nd,edf->enf", x, lw["w_up"])
        y = ein("enf,efd->end", jax.nn.silu(g) * u, lw["w_down"])
        y = jnp.einsum("ne,end->nd", combine, y,
                       precision=jax.lax.Precision.HIGHEST)
        return y.reshape(B, S, d), margin
    return ffn


def forward(w, tokens, conf, ein=highest, margins=False):
    """tokens (B, S) int32 -> (final hidden states (B, S, d), float32; (k,
    v) of the last layer), as ``qwen3.forward``; with ``margins`` also
    each layer's router margin of each token, (L, B, S)."""
    c = _conf(conf)
    eps = c["rms_norm_eps"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    ffn = moe(c["top_k"])

    def layer(carry, lw):
        x, _ = carry
        a, kv = qwen3.attention(lw, qwen3.rmsnorm(x, lw["ln1"], eps), pos,
                                c, ein)
        x = x + a
        y, margin = ffn(lw, qwen3.rmsnorm(x, lw["ln2"], eps), ein)
        return (x + y, kv), margin

    m = dims(conf)
    kv0 = (jnp.zeros((B, S, m["K"], m["hd"]), F32),) * 2
    (x, kv_last), mg = jax.lax.scan(layer, (x, kv0), w["layers"])
    h = qwen3.rmsnorm(x, w["final_norm"].astype(F32), eps)
    return (h, kv_last, mg) if margins else (h, kv_last)


def logits(w, h, conf, ein=highest):
    return qwen3.logits(w, h, _conf(conf), ein)
