"""Plain float32 reference of the Jamba hybrid decoder
(ai21labs/AI21-Jamba2-Mini, ``transformers``' ``modeling_jamba.py``).

Every layer is a pre-norm mixer followed by a pre-norm feed-forward, each
added to the residual stream; RMSNorm with eps ``rms_norm_eps`` throughout.
Which mixer and which feed-forward a layer has follows the published
periods and offsets (``JambaConfig.layers_block_type`` and
``layers_num_experts``), counted from the configuration's ``first_layer``
(0 for a whole model; where a cut of the layers starts):

* attention: causal grouped-query attention with no positional encoding,
  scores scaled by 1/sqrt(head size);
* Mamba-1 (``JambaMambaMixer.slow_forward``): the input projection to x and
  the gate z, a depthwise causal convolution with bias and SiLU, ``x_proj``
  to dt, B and C, each through its own RMSNorm with a learned gain, dt
  through ``dt_proj`` with bias and softplus, then the recurrence
  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t and y_t = h_t C_t + D x_t, one
  token at a time (a ``lax.scan`` over tokens), gated by SiLU(z) and
  projected out;
* a dense SwiGLU, or the sparse MoE: a float32 router, softmax over the
  experts, the top ``num_experts_per_tok`` probabilities kept as gates
  without dividing by their sum, every expert run over every token and
  combined by the gates (zero for experts not chosen).  Nothing is dropped.

Written plainly, and in blocks where a whole tensor would not fit one
chip: attention a block of queries at a time against every key, the
experts a block of tokens at a time.  It imports nothing of the program
under test.  Departures from the published model: none in the
mathematics.  Weights are random from a seed (see ``weight_specs``), not
the released checkpoint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from references import qwen3

F32 = jnp.float32
highest = qwen3.highest
rmsnorm = qwen3.rmsnorm
ATTN_BLOCK = 512      # queries a block of the reference's attention
MOE_BLOCK = 1024      # tokens a block of the reference's experts


def layer_kinds(conf):
    """(mixer, feed-forward) of each layer: ``attention`` or ``mamba``,
    ``moe`` or ``mlp``."""
    first = conf.get("first_layer", 0)
    out = []
    for i in range(first, first + conf["num_hidden_layers"]):
        mix = "attention" if i % conf["attn_layer_period"] == \
            conf["attn_layer_offset"] else "mamba"
        ffn = "moe" if conf["num_experts"] > 1 and \
            i % conf["expert_layer_period"] == conf["expert_layer_offset"] \
            else "mlp"
        out.append((mix, ffn))
    return out


def dims(conf):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    kinds = layer_kinds(conf)
    return dict(L=conf["num_hidden_layers"], d=d, H=H,
                K=conf["num_key_value_heads"], hd=d // H,
                ff=conf["intermediate_size"], V=conf["vocab_size"],
                E=conf["num_experts"], top_k=conf["num_experts_per_tok"],
                di=conf["mamba_expand"] * d, N=conf["mamba_d_state"],
                conv=conf["mamba_d_conv"], dtr=conf["mamba_dt_rank"],
                kinds=kinds,
                n_attn=sum(m == "attention" for m, _ in kinds),
                n_moe=sum(f == "moe" for _, f in kinds))


def weight_specs(conf):
    """Canonical weights: name -> (shape, kind, std), as
    ``qwen3.weight_specs``.  Each kind of sublayer is stacked over the
    layers that have it, in layer order: ``attn_`` over the attention
    layers, ``mamba_`` over the Mamba layers, ``moe_`` and ``mlp_`` over
    the layers with each feed-forward; the experts' leaves have four axes,
    layer then expert, and hold the gate and up projections as
    ``transformers`` does, (out, in): a chip then reads them in place."""
    m = dims(conf)
    L, d, H, K, hd, ff, V, E = (m[k] for k in ("L", "d", "H", "K", "hd",
                                               "ff", "V", "E"))
    di, N, Kc, dtr = m["di"], m["N"], m["conv"], m["dtr"]
    na, ne = m["n_attn"], m["n_moe"]
    nm, nd = L - na, L - ne
    specs = {
        "embed": ((V, d), "normal", d ** -0.5),
        "final_norm": ((d,), "scale", 0.1),
        "layers/ln1": ((L, d), "scale", 0.1),
        "layers/ln2": ((L, d), "scale", 0.1),
    }
    if na:
        specs.update({
            "layers/attn_wq": ((na, d, H * hd), "normal", d ** -0.5),
            "layers/attn_wk": ((na, d, K * hd), "normal", d ** -0.5),
            "layers/attn_wv": ((na, d, K * hd), "normal", d ** -0.5),
            "layers/attn_wo": ((na, H * hd, d), "normal", (H * hd) ** -0.5),
        })
    if nm:
        specs.update({
            "layers/mamba_in_proj": ((nm, d, 2 * di), "normal", d ** -0.5),
            "layers/mamba_conv_w": ((nm, di, Kc), "normal", Kc ** -0.5),
            "layers/mamba_x_proj": ((nm, di, dtr + 2 * N), "normal",
                                    di ** -0.5),
            "layers/mamba_dt_norm": ((nm, dtr), "scale", 0.1),
            "layers/mamba_B_norm": ((nm, N), "scale", 0.1),
            "layers/mamba_C_norm": ((nm, N), "scale", 0.1),
            "layers/mamba_dt_proj": ((nm, dtr, di), "normal", dtr ** -0.5),
            "layers/mamba_dt_bias": ((nm, di), "normal", 1.0),
            "layers/mamba_A_log": ((nm, di, N), "normal", 2.0),
            "layers/mamba_D": ((nm, di), "scale", 0.1),
            "layers/mamba_out_proj": ((nm, di, d), "normal", di ** -0.5),
        })
        if conf["mamba_conv_bias"]:
            specs["layers/mamba_conv_b"] = ((nm, di), "normal", 0.1)
    if ne:
        specs.update({
            "layers/moe_router": ((ne, d, E), "normal", d ** -0.5),
            "layers/moe_w_gate": ((ne, E, ff, d), "normal", d ** -0.5),
            "layers/moe_w_up": ((ne, E, ff, d), "normal", d ** -0.5),
            "layers/moe_w_down": ((ne, E, ff, d), "normal", ff ** -0.5),
        })
    if nd:
        specs.update({
            "layers/mlp_w_gate": ((nd, d, ff), "normal", d ** -0.5),
            "layers/mlp_w_up": ((nd, d, ff), "normal", d ** -0.5),
            "layers/mlp_w_down": ((nd, ff, d), "normal", ff ** -0.5),
        })
    if not conf["tie_word_embeddings"]:
        specs["unembed"] = ((V, d), "normal", d ** -0.5)
    return specs


def _blocks(n, block):
    """The largest divisor of ``n`` no larger than ``block``."""
    b = min(block, n)
    while n % b:
        b -= 1
    return b


def attention(lw, h, conf, ein):
    """Causal GQA self-attention without positions.  Returns (output,
    (k, v)), k and v as a decode cache holds them."""
    m = dims(conf)
    B, S, _ = h.shape
    H, K, hd = m["H"], m["K"], m["hd"]
    q = ein("bsd,de->bse", h, lw["attn_wq"]).reshape(B, S, K, H // K, hd)
    k = ein("bsd,de->bse", h, lw["attn_wk"]).reshape(B, S, K, hd)
    v = ein("bsd,de->bse", h, lw["attn_wv"]).reshape(B, S, K, hd)
    c = _blocks(S, ATTN_BLOCK)

    def block(args):
        qb, i = args                                    # (B, c, K, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        causal = (i * c + jnp.arange(c))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskh->bqkgh", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    qs = jnp.moveaxis(q.reshape(B, S // c, c, K, H // K, hd), 1, 0)
    ctx = jax.lax.map(block, (qs, jnp.arange(S // c)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, H * hd)
    return ein("bse,ed->bsd", ctx, lw["attn_wo"]), (k, v)


def mamba(lw, h, conf, ein):
    """The Mamba-1 mixer of ``JambaMambaMixer.slow_forward``."""
    m = dims(conf)
    B, S, _ = h.shape
    di, N, Kc, dtr = m["di"], m["N"], m["conv"], m["dtr"]
    eps = conf["rms_norm_eps"]
    xz = ein("bsd,de->bse", h, lw["mamba_in_proj"])
    x, z = xz[..., :di], xz[..., di:]
    xpad = jnp.pad(x, ((0, 0), (Kc - 1, 0), (0, 0)))
    w = lw["mamba_conv_w"].astype(F32)
    xc = sum(xpad[:, j:j + S] * w[:, j] for j in range(Kc))
    if "mamba_conv_b" in lw:
        xc = xc + lw["mamba_conv_b"].astype(F32)
    xc = jax.nn.silu(xc)
    dbc = ein("bse,ek->bsk", xc, lw["mamba_x_proj"])
    dt = rmsnorm(dbc[..., :dtr], lw["mamba_dt_norm"].astype(F32), eps)
    Bm = rmsnorm(dbc[..., dtr:dtr + N], lw["mamba_B_norm"].astype(F32), eps)
    Cm = rmsnorm(dbc[..., dtr + N:], lw["mamba_C_norm"].astype(F32), eps)
    dt = jax.nn.softplus(ein("bsr,re->bse", dt, lw["mamba_dt_proj"])
                         + lw["mamba_dt_bias"].astype(F32))
    A = -jnp.exp(lw["mamba_A_log"].astype(F32))            # (di, N)

    def step(state, inp):
        dt_t, b_t, c_t, x_t = inp            # (B, di), (B, N), (B, N), (B, di)
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + dt_t[..., None] * b_t[:, None] * x_t[..., None])
        return state, jnp.einsum("bdn,bn->bd", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, Bm, Cm, xc))
    _, y = jax.lax.scan(step, jnp.zeros((B, di, N), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + xc * lw["mamba_D"].astype(F32)
    return ein("bse,ed->bsd", y * jax.nn.silu(z), lw["mamba_out_proj"])


def mlp(lw, h, ein):
    g = ein("bsd,df->bsf", h, lw["mlp_w_gate"])
    u = ein("bsd,df->bsf", h, lw["mlp_w_up"])
    return ein("bsf,fd->bsd", jax.nn.silu(g) * u, lw["mlp_w_down"])


def moe(lw, h, top_k, ein):
    """The sparse MoE feed-forward: (output, router margin of each token).
    The margin is the gap between the ``top_k``-th largest router logit
    and the next: a rounding of the router's input by about that much
    changes the token's experts."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    logits = jnp.einsum("nd,de->ne", x, lw["moe_router"].astype(F32),
                        precision=jax.lax.Precision.HIGHEST)
    top = jax.lax.top_k(logits, top_k + 1)[0]
    margin = (top[:, top_k - 1] - top[:, top_k]).reshape(B, S)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    E = probs.shape[-1]
    combine = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * gates[..., None],
                      axis=1)                                   # (N, E)

    def block(args):
        xb, cb = args
        g = ein("nd,efd->enf", xb, lw["moe_w_gate"])
        u = ein("nd,efd->enf", xb, lw["moe_w_up"])
        y = ein("enf,efd->end", jax.nn.silu(g) * u, lw["moe_w_down"])
        return jnp.einsum("ne,end->nd", cb, y,
                          precision=jax.lax.Precision.HIGHEST)

    n = _blocks(B * S, MOE_BLOCK)
    y = jax.lax.map(block, (x.reshape(-1, n, d), combine.reshape(-1, n, E)))
    return y.reshape(B, S, d), margin


def forward(w, tokens, conf, ein=highest, margins=False):
    """tokens (B, S) int32 -> (final hidden states (B, S, d) after the final
    norm, float32; (k, v) of the last attention layer); with ``margins``
    also each layer's router margin of each token, (L, B, S): ``+inf`` on
    the layers with no router.  Layers run one at a time (a scan over the
    layers, each taking its own weights by index), so that no layer's
    weights are copied out beside the stacks."""
    m = dims(conf)
    eps = conf["rms_norm_eps"]
    B, S = tokens.shape
    lws = w["layers"]
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    kinds = sorted(set(m["kinds"]))
    seen, order = {}, []
    for mix, ffn in m["kinds"]:
        order.append((kinds.index((mix, ffn)), seen.get(mix, 0),
                      seen.get(ffn, 0)))
        seen[mix], seen[ffn] = seen.get(mix, 0) + 1, seen.get(ffn, 0) + 1

    def own(prefix, j):
        return {k: jax.lax.dynamic_index_in_dim(v, j, keepdims=False)
                for k, v in lws.items() if k.startswith(prefix)}

    def branch(mix, ffn):
        def run(x, kv, i, j, k):
            h = rmsnorm(x, lws["ln1"][i].astype(F32), eps)
            if mix == "attention":
                a, kv = attention(own("attn_", j), h, conf, ein)
            else:
                a = mamba(own("mamba_", j), h, conf, ein)
            x = x + a
            h = rmsnorm(x, lws["ln2"][i].astype(F32), eps)
            if ffn == "moe":
                y, margin = moe(own("moe_", k), h, m["top_k"], ein)
            else:
                y, margin = mlp(own("mlp_", k), h, ein), \
                    jnp.full((B, S), jnp.inf, F32)
            return x + y, kv, margin
        return run

    def layer(carry, inp):
        x, kv = carry
        i, (kind, j, k) = inp
        x, kv, margin = jax.lax.switch(kind, [branch(*c) for c in kinds],
                                       x, kv, i, j, k)
        return (x, kv), margin

    kv0 = (jnp.zeros((B, S, m["K"], m["hd"]), F32),) * 2
    (x, kv), mg = jax.lax.scan(
        layer, (x, kv0), (jnp.arange(m["L"]), tuple(jnp.asarray(order).T)))
    h = rmsnorm(x, w["final_norm"].astype(F32), eps)
    return (h, kv, mg) if margins else (h, kv)


def logits(w, h, conf, ein=highest):
    return qwen3.logits(w, h, conf, ein)
