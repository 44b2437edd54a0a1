"""Config registry: one module per assigned architecture.

``get_config(name)`` returns the full published config; ``get_reduced(name)``
returns a tiny same-family config for CPU smoke tests.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import ArchConfig, MoEConfig, SSMConfig, RGLRUConfig, SHAPES, ShapeConfig, shape_applicable

from repro.configs import (
    musicgen_medium,
    dbrx_132b,
    llama4_maverick_400b_a17b,
    smollm_135m,
    qwen3_4b,
    h2o_danube_3_4b,
    olmo_1b,
    recurrentgemma_9b,
    falcon_mamba_7b,
    qwen2_vl_72b,
    jamba2_mini,
)

_MODULES = {
    "musicgen-medium": musicgen_medium,
    "dbrx-132b": dbrx_132b,
    "llama4-maverick-400b-a17b": llama4_maverick_400b_a17b,
    "smollm-135m": smollm_135m,
    "qwen3-4b": qwen3_4b,
    "h2o-danube-3-4b": h2o_danube_3_4b,
    "olmo-1b": olmo_1b,
    "recurrentgemma-9b": recurrentgemma_9b,
    "falcon-mamba-7b": falcon_mamba_7b,
    "qwen2-vl-72b": qwen2_vl_72b,
    "jamba2-mini": jamba2_mini,
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str) -> ArchConfig:
    return _MODULES[name].CONFIG


def get_reduced(name: str) -> ArchConfig:
    return _MODULES[name].reduced()


def applicable_shapes(name: str):
    arch = get_config(name)
    return [s for s in SHAPES.values() if shape_applicable(arch, s)]


__all__ = [
    "ArchConfig", "MoEConfig", "SSMConfig", "RGLRUConfig",
    "SHAPES", "ShapeConfig", "shape_applicable",
    "ARCH_NAMES", "get_config", "get_reduced", "applicable_shapes",
]
