"""Operations a prefill of a hybrid Mamba / attention / MoE stack needs,
from the shapes alone (``references/jamba.dims``: ``kinds`` gives each
layer's mixer and feed-forward).  A multiply-add counts as two operations,
as in ``flops.py``; only the model's own mathematics counts: the top-k
experts a token is routed to, not the capacity slots the program pads to.
"""

from __future__ import annotations

from chipbench import flops


def mamba_token_flops(m) -> float:
    """One token through one Mamba-1 mixer: the in, x, dt and out
    projections, the depthwise convolution, and the scan (dt A, dt B x,
    the state update and the readout by C: 7 operations a state)."""
    d, di, N, dtr = m["d"], m["di"], m["N"], m["dtr"]
    proj = 2 * (d * 2 * di + di * (dtr + 2 * N) + dtr * di + di * d)
    return proj + 2 * m["conv"] * di + 7 * di * N


def attention_proj_flops(m) -> float:
    d, H, K, hd = m["d"], m["H"], m["K"], m["hd"]
    return 2 * (d * H * hd + 2 * d * K * hd + H * hd * d)


def ffn_token_flops(m, kind: str) -> float:
    """The routed experts (and the router) or the dense SwiGLU."""
    d, ff = m["d"], m["ff"]
    if kind == "moe":
        return m["top_k"] * 2 * 3 * d * ff + 2 * d * m["E"]
    return 2 * 3 * d * ff


def prefill_flops(m, prompt_tokens: int) -> float:
    """One prompt: every layer's mixer and feed-forward over each token,
    causal attention (token i attends to i + 1 keys) in the attention
    layers, and one row of logits for its last position."""
    P = prompt_tokens
    per_token = sum((attention_proj_flops(m) if mix == "attention"
                     else mamba_token_flops(m)) + ffn_token_flops(m, ffn)
                    for mix, ffn in m["kinds"])
    attn = m["n_attn"] * 4 * m["H"] * m["hd"] * P * (P + 1) / 2
    return P * per_token + attn + flops.head_flops(m)
