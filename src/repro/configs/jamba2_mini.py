"""AI21-Jamba2-Mini: hybrid Mamba-1 / attention with top-2 MoE.

[hf:ai21labs/AI21-Jamba2-Mini config.json, model_type jamba]  32L,
d_model=4096, 32 heads (GQA kv=8, head 128), vocab=65536, untied head,
rms_norm_eps 1e-6.  One attention layer in every 8 (``attn_layer_period``
8, ``attn_layer_offset`` 4) and Mamba-1 mixers in the rest (d_state 16,
d_conv 4, expand 2, dt_rank 256, conv bias, no projection bias, RMSNorms on
dt, B and C); every block is followed by a feed-forward: 16 experts top-2
of width 14336 on odd layers (``expert_layer_period`` 2, offset 1), a
dense SwiGLU of the same width on even ones.  The layer order is
``transformers``' ``JambaConfig.layers_block_type``.  The attention has no
positional encoding, and the top-2 gates are the softmax probabilities,
not divided by their sum.  About 52 B parameters, 12 B active.

Dropless as published: ``capacity_factor`` = experts / top_k gives every
expert room for every token.
"""

import dataclasses

from repro.configs.base import ArchConfig, MoEConfig, SSMConfig

F, T = False, True

CONFIG = ArchConfig(
    name="jamba2-mini",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab_size=65536,
    pattern=("mamba",) * 4 + ("attn",) + ("mamba",) * 3,
    moe=MoEConfig(n_experts=16, top_k=2, capacity_factor=8.0,
                  normalize_gates=False),
    moe_pattern=(F, T) * 4,
    ssm=SSMConfig(state_dim=16, expand=2, conv_dim=4, dt_rank=256,
                  inner_norms=True),
    rope=False,
    subquadratic=False,
)


def stage(first: int, n_layers: int) -> ArchConfig:
    """Layers ``first`` to ``first + n_layers`` of the model, as one stage
    of a pipeline runs them: the pattern read from layer ``first``."""
    k = first % len(CONFIG.pattern)
    return dataclasses.replace(
        CONFIG, n_layers=n_layers,
        pattern=CONFIG.pattern[k:] + CONFIG.pattern[:k],
        moe_pattern=CONFIG.moe_pattern[k:] + CONFIG.moe_pattern[:k])


def reduced() -> ArchConfig:
    """Two layers, one of each kind Jamba pairs: attention with a dense
    SwiGLU, then Mamba with the MoE."""
    return dataclasses.replace(
        CONFIG, name="jamba2-mini-reduced",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, vocab_size=128, pattern=("attn", "mamba"),
        moe_pattern=(False, True),
        moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0,
                      normalize_gates=False),
        ssm=SSMConfig(state_dim=4, expand=2, conv_dim=4, dt_rank=8,
                      inner_norms=True),
    )
