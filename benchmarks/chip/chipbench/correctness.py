"""The comparison that decides ``correct``, and its lower-precision control.

A served token is judged by the plain float32 reference run over the
prompt and the tokens served before it: the gap by which the served
token's reference logit lies below the reference's best logit at that
position.  Greedy decoding in the configuration's precision (bfloat16)
leaves gaps of rounding size; a wrong token, a wrong cache or a wrong
expert leaves gaps of the logits' own size.

The control is the same reference with every linear layer computed in
float8 (e4m3, one scale per tensor for each operand): the step below
bfloat16.  It does not decode: at each position of the same sequences, the
gap of the token that the float8 logits put first is read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(F32) * scale


def fp8_ein(spec, a, b):
    """A contraction with both operands rounded to float8 e4m3."""
    return jnp.einsum(spec, _fp8(a), _fp8(b),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def served_gaps(ref, w, conf, tokens, next_tokens, mask, control=False):
    """Per-position gaps over sequences, one sequence at a time.

    tokens (n, S): prompt and served tokens, padded; next_tokens (n, S):
    the served token each position produced (its reference logit is
    judged); mask (n, S): positions that produced a served token.  With
    ``control``, the token judged is the float8 control's first choice
    instead.  Returns (n, S) float32 gaps, 0 where not masked."""
    def one(args):
        tok, nxt, msk = args
        h, _ = ref.forward(w, tok[None], conf)
        lg = ref.logits(w, h[0], conf)                      # (S, V)
        if control:
            hc, _ = ref.forward(w, tok[None], conf, ein=fp8_ein)
            nxt = jnp.argmax(ref.logits(w, hc[0], conf, ein=fp8_ein), -1)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
        return jnp.where(msk, best - got, 0.0)

    return jax.lax.map(one, (tokens, next_tokens, mask))


def prefill_errors(ref, w, conf, tokens, logits, kv, control=False):
    """Per prompt, what the program's prefill made (or with ``control``
    the float8 control's) against the reference, token by token:

    * ``kv_err`` (n, S): each token's relative error of the last layer's
      k and v, the larger of the two;
    * ``logit_err`` (n,): the relative error of the last position's
      logits;
    * ``margin`` (L, n, S): the reference's router margin of each token in
      each layer (``references/dbrx.forward``), by which the caller leaves
      out tokens whose experts a rounding may change.

    tokens (n, S); logits (n, V); kv: (k, v) each (n, S, K, hd)."""
    def rel(a, b, axes):
        a, b = a.astype(F32), b.astype(F32)
        return jnp.sqrt(jnp.sum((a - b) ** 2, axes) / jnp.sum(b * b, axes))

    def one(args):
        tok, lg_p, k, v = args
        h, (rk, rv), margin = ref.forward(w, tok[None], conf, margins=True)
        lg = ref.logits(w, h[0, -1], conf)
        if control:
            hc, (k, v) = ref.forward(w, tok[None], conf, ein=fp8_ein)
            k, v = k[0], v[0]
            lg_p = ref.logits(w, hc[0, -1], conf, ein=fp8_ein)
        kv_err = jnp.maximum(rel(k, rk[0], (-2, -1)), rel(v, rv[0], (-2, -1)))
        return {"kv_err": kv_err, "logit_err": rel(lg_p, lg, None),
                "margin": margin[:, 0]}

    out = jax.lax.map(one, (tokens, logits, kv[0], kv[1]))
    out["margin"] = jnp.moveaxis(out["margin"], 0, 1)
    return out
