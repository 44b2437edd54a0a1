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


def prefill_readings(ref, w, conf, tokens, first, kv, control=False):
    """Per prompt: the gap of the first token (the program's, or with
    ``control`` the float8 control's) and the relative error of the last
    layer's k and v (the program's cache, or the control's) against the
    reference, the larger of the two.

    tokens (n, S); first (n,); kv: (k, v) each (n, S, K, hd).  Returns
    (gaps (n,), kv_rel (n,))."""
    def one(args):
        tok, fst, k, v = args
        h, (rk, rv) = ref.forward(w, tok[None], conf)
        lg = ref.logits(w, h[0, -1], conf)
        if control:
            hc, (k, v) = ref.forward(w, tok[None], conf, ein=fp8_ein)
            k, v = k[0], v[0]
            fst = jnp.argmax(ref.logits(w, hc[0, -1], conf, ein=fp8_ein))
        gap = jnp.max(lg) - lg[fst]

        def rel(a, b):
            a, b = a.astype(F32), b.astype(F32)
            return jnp.linalg.norm(a - b) / jnp.linalg.norm(b)
        return gap, jnp.maximum(rel(k, rk[0]), rel(v, rv[0]))

    return jax.lax.map(one, (tokens, first, kv[0], kv[1]))
