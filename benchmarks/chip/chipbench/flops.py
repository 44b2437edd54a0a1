"""Operations and bytes a model step needs, from the shapes alone.

``m`` is a reference's ``dims(conf)``: L layers, d model width, H query
heads, K key/value heads, hd head size, ff feed-forward width, V vocabulary,
and for a mixture of experts E experts of which top_k run per token.  A
multiply-add counts as two operations.  Only the model's own mathematics
counts: padding, inactive batch rows and recomputation do not.
"""

from __future__ import annotations


def token_flops(m) -> float:
    """One token through every layer, without attention over the context:
    the q/k/v/o projections and the feed-forward (top_k experts and the
    router for a mixture of experts)."""
    d, H, K, hd, ff = m["d"], m["H"], m["K"], m["hd"], m["ff"]
    proj = 2 * (d * H * hd + 2 * d * K * hd + H * hd * d)
    if "E" in m:
        ffn = m["top_k"] * 2 * 3 * d * ff + 2 * d * m["E"]
    else:
        ffn = 2 * 3 * d * ff
    return m["L"] * (proj + ffn)


def attention_flops(m, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` keys, every
    layer."""
    return m["L"] * 4 * m["H"] * m["hd"] * keys


def head_flops(m) -> float:
    """One row of logits."""
    return 2 * m["d"] * m["V"]


def prefill_flops(m, prompt_tokens: int) -> float:
    """One prompt: causal attention (token i attends to i + 1 keys) and
    one row of logits, for its last position."""
    P = prompt_tokens
    return (P * token_flops(m) + attention_flops(m, P * (P + 1) / 2)
            + head_flops(m))


def decode_flops(m, keys: int) -> float:
    """One decoded token whose query attends to ``keys`` keys."""
    return token_flops(m) + attention_flops(m, keys) + head_flops(m)


BF16_BYTES = 2   # weights and cache are served in bfloat16


def kv_bytes_per_token(m) -> int:
    return m["L"] * 2 * m["K"] * m["hd"] * BF16_BYTES


def weight_bytes(specs) -> int:
    total = 0
    for shape, _, _ in specs.values():
        n = 1
        for s in shape:
            n *= s
        total += n * BF16_BYTES
    return total
