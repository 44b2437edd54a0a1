"""Plain float32 reference of the Qwen3 dense decoder (Qwen/Qwen3-4B).

Written from the published description, in straightforward ``jax.numpy``:
pre-norm decoder layers with RMSNorm (eps ``rms_norm_eps``), grouped-query
attention with a per-head RMSNorm on queries and keys (qk-norm) before
rotary embeddings of the half-split ("rotate_half") form, causal softmax
attention, a SwiGLU MLP, a final RMSNorm and the output head (the embedding
table when ``tie_word_embeddings``).  No cache, no batching tricks, no
kernels.  It imports nothing of the program under test.

Departures from the published model: none in the mathematics.  Weights are
random from a seed (see ``weight_specs``), not the released checkpoint.

``ein`` is the one contraction every linear layer goes through: float32 at
``highest`` precision by default; the lower-precision control passes its
own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(conf):
    return dict(L=conf["num_hidden_layers"], d=conf["hidden_size"],
                H=conf["num_attention_heads"], K=conf["num_key_value_heads"],
                hd=conf["head_dim"], ff=conf["intermediate_size"],
                V=conf["vocab_size"])


def weight_specs(conf):
    """Canonical weights: name -> (shape, kind, std).  ``layers/`` leaves
    carry the layer index first.  ``normal`` leaves are N(0, std^2);
    ``scale`` leaves (norm gains) are 1 + N(0, std^2)."""
    m = dims(conf)
    L, d, H, K, hd, ff, V = (m[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                            "V"))
    specs = {
        "embed": ((V, d), "normal", d ** -0.5),
        "final_norm": ((d,), "scale", 0.1),
        "layers/ln1": ((L, d), "scale", 0.1),
        "layers/ln2": ((L, d), "scale", 0.1),
        "layers/wq": ((L, d, H * hd), "normal", d ** -0.5),
        "layers/wk": ((L, d, K * hd), "normal", d ** -0.5),
        "layers/wv": ((L, d, K * hd), "normal", d ** -0.5),
        "layers/wo": ((L, H * hd, d), "normal", (H * hd) ** -0.5),
        "layers/q_norm": ((L, hd), "scale", 0.1),
        "layers/k_norm": ((L, hd), "scale", 0.1),
        "layers/w_gate": ((L, d, ff), "normal", d ** -0.5),
        "layers/w_up": ((L, d, ff), "normal", d ** -0.5),
        "layers/w_down": ((L, ff, d), "normal", ff ** -0.5),
    }
    if not conf["tie_word_embeddings"]:
        specs["unembed"] = ((V, d), "normal", d ** -0.5)
    return specs


def highest(spec, a, b):
    """Float32 contraction at full precision; bf16 weights are cast as
    they are read."""
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def rmsnorm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (B, S, N, hd); rotate_half form over the two halves of hd."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, :, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(lw, h, pos, conf, ein):
    """Causal GQA self-attention.  Returns (output, (k, v)) with k after
    qk-norm and rotary embedding, as a decode cache holds it."""
    m = dims(conf)
    B, S, _ = h.shape
    H, K, hd = m["H"], m["K"], m["hd"]
    eps = conf.get("rms_norm_eps", 1e-6)
    q = ein("bsd,de->bse", h, lw["wq"]).reshape(B, S, H, hd)
    k = ein("bsd,de->bse", h, lw["wk"]).reshape(B, S, K, hd)
    v = ein("bsd,de->bse", h, lw["wv"]).reshape(B, S, K, hd)
    if "q_norm" in lw:
        q = rmsnorm(q, lw["q_norm"], eps)
        k = rmsnorm(k, lw["k_norm"], eps)
    theta = conf.get("rope_theta", 10000.0)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    out = ein("bse,ed->bsd", ctx.reshape(B, S, H * hd), lw["wo"])
    return out, (k, v)


def mlp(lw, h, ein):
    g = ein("bsd,df->bsf", h, lw["w_gate"])
    u = ein("bsd,df->bsf", h, lw["w_up"])
    return ein("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"])


def forward(w, tokens, conf, ein=highest, ffn=mlp):
    """tokens (B, S) int32 -> (final hidden states (B, S, d) after the final
    norm, float32; (k, v) of the last layer).  Layers run one at a time
    (a scan); weights are cast to float32 where ``ein`` reads them."""
    eps = conf.get("rms_norm_eps", 1e-6)
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def layer(carry, lw):
        x, _ = carry
        a, kv = attention(lw, rmsnorm(x, lw["ln1"], eps), pos, conf, ein)
        x = x + a
        x = x + ffn(lw, rmsnorm(x, lw["ln2"], eps), ein)
        return (x, kv), None

    m = dims(conf)
    kv0 = (jnp.zeros((B, S, m["K"], m["hd"]), F32),) * 2
    (x, kv_last), _ = jax.lax.scan(layer, (x, kv0), w["layers"])
    return rmsnorm(x, w["final_norm"].astype(F32), eps), kv_last


def head(w, conf):
    return w["embed"] if conf["tie_word_embeddings"] else w["unembed"]


def logits(w, h, conf, ein=highest):
    """(..., d) final hidden states -> (..., V) float32 logits."""
    return ein("...d,vd->...v", h, head(w, conf))
