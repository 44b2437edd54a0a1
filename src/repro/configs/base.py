"""Architecture configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig`.  Configs
are plain dataclasses (no framework deps) consumed by ``models.transformer``
to build the parameter pytree and the forward functions, and by
``launch.dryrun`` to build ``input_specs``.

Block kinds
-----------
A model is a sequence of *blocks*.  Most architectures are homogeneous
(``pattern`` of length 1); RecurrentGemma uses a 1:2 local-attention /
RG-LRU pattern.  Supported kinds:

* ``"attn"``    — self-attention (GQA / MHA / MQA, optional qk-norm, M-RoPE)
* ``"swa"``     — sliding-window self-attention (banded; sub-quadratic)
* ``"rglru"``   — RG-LRU recurrent block (RecurrentGemma)
* ``"mamba"``   — Mamba-1 selective-scan block (attention free)

Every block is a mixer of its kind followed by a feed-forward, dense or MoE
by its position in ``moe_pattern`` (none where ``d_ff`` and ``dense_ff``
are 0, as in Falcon-Mamba).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    # Token capacity per expert = capacity_factor * tokens / n_experts.
    capacity_factor: float = 1.25
    # divide the top-k gates by their sum (DBRX, Mixtral); Jamba keeps the
    # raw softmax probabilities of the chosen experts
    normalize_gates: bool = True


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 16       # N: per-channel state size
    expand: int = 2           # d_inner = expand * d_model
    conv_dim: int = 4         # depthwise causal conv width
    dt_rank: int = 0          # 0 -> ceil(d_model / 16)
    # RMSNorms with learned gains on dt, B and C after x_proj (Jamba)
    inner_norms: bool = False


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0        # 0 -> d_model
    conv_dim: int = 4
    block_width: int = 0      # RG-LRU diagonal block size (unused placeholder)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int              # 0 for attention-free archs
    n_kv_heads: int
    d_ff: int                 # per-expert d_ff when MoE
    vocab_size: int
    head_dim: int = 0         # 0 -> d_model // n_heads
    pattern: Tuple[str, ...] = ("attn",)   # repeating block-kind pattern
    moe: Optional[MoEConfig] = None
    # which pattern positions carry the MoE FFN (None -> all, when moe set);
    # llama4 interleaves dense and MoE layers 1:1
    moe_pattern: Optional[Tuple[bool, ...]] = None
    dense_ff: int = 0          # d_ff of non-MoE positions (0 -> d_ff)
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # Attention flavour flags.
    qk_norm: bool = False
    sliding_window: int = 0   # 0 -> full attention for "attn" kind
    local_window: int = 2048  # window for "swa" blocks / RG local attention
    mrope: bool = False       # M-RoPE (sections over head_dim; Qwen2-VL)
    mrope_sections: Tuple[int, ...] = (16, 24, 24)  # t, h, w half-dim splits
    non_parametric_ln: bool = False   # OLMo-style LN without learned scale
    rope: bool = True         # rotary positions on q and k (Jamba: none)
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    # Modality frontend stub: "none" | "audio_tokens" | "vision_patches".
    frontend: str = "none"
    # Does the arch support O(seq) decode state (=> long_500k runnable)?
    subquadratic: bool = False

    # ---- derived ----
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_attention_free(self) -> bool:
        return all(k == "mamba" for k in self.pattern)

    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kinds, pattern repeated/truncated to n_layers."""
        reps = (self.n_layers + len(self.pattern) - 1) // len(self.pattern)
        return tuple((self.pattern * reps)[: self.n_layers])

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), exact enough
        for MODEL_FLOPS bookkeeping."""
        d, v = self.d_model, self.vocab_size
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d  # unembedding
        hd = self.resolved_head_dim
        for kind in self.block_kinds():
            if kind in ("attn", "swa"):
                q = d * self.n_heads * hd
                kv = 2 * d * self.n_kv_heads * hd
                o = self.n_heads * hd * d
                total += q + kv + o
            elif kind == "rglru":
                w = self.rglru.lru_width or d
                # in/out proj + gates + conv
                total += 2 * d * w + 2 * w + w * self.rglru.conv_dim + 2 * w * w // 1
            elif kind == "mamba":
                di = self.ssm.expand * d
                dtr = self.ssm.dt_rank or -(-d // 16)
                total += (d * 2 * di            # in_proj (x and z)
                          + di * self.ssm.conv_dim
                          + di * (dtr + 2 * self.ssm.state_dim)  # x -> dt,B,C
                          + dtr * di            # dt_proj
                          + di * self.ssm.state_dim  # A
                          + di                  # D
                          + di * d)             # out_proj
                if self.ssm.inner_norms:
                    total += dtr + 2 * self.ssm.state_dim
            total += 2 * d  # norms
        # FFN params (kind- and position-aware)
        plen = len(self.pattern)
        for i in range(self.n_layers):
            is_moe = self.moe is not None and (
                self.moe_pattern is None or self.moe_pattern[i % plen])
            if is_moe:
                total += self.moe.n_experts * 3 * d * self.d_ff \
                    + d * self.moe.n_experts
            else:
                ff = self.dense_ff or self.d_ff
                if ff:
                    total += 3 * d * ff
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        plen = len(self.pattern)
        n_moe = sum(1 for i in range(self.n_layers)
                    if self.moe_pattern is None or self.moe_pattern[i % plen])
        all_experts = n_moe * self.moe.n_experts * 3 * d * self.d_ff
        active = n_moe * self.moe.top_k * 3 * d * self.d_ff
        return total - all_experts + active


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(arch: ArchConfig, shape: ShapeConfig) -> bool:
    """long_500k needs sub-quadratic attention / bounded decode state."""
    if shape.name == "long_500k":
        return arch.subquadratic
    return True
