"""The program's view of a DBRX configuration: its ``ArchConfig`` (the MoE
capacity from the configuration's ``run`` settings) and its parameter
tree, filled from the benchmark's canonical weights."""

from __future__ import annotations

from repro.configs.base import ArchConfig, MoEConfig

from adapters.qwen3 import attention_params
from references import dbrx as ref


def arch_config(conf) -> ArchConfig:
    m = ref.dims(conf)
    return ArchConfig(
        name=conf["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["V"],
        moe=MoEConfig(n_experts=m["E"], top_k=m["top_k"],
                      capacity_factor=float(conf["run"]["capacity_factor"])),
        rope_theta=float(conf["attn_config"]["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]))


def program_params(canon, conf):
    m = ref.dims(conf)
    lw = canon["layers"]
    out = {"embed": {"table": canon["embed"]},
           "final_norm": {"scale": canon["final_norm"]},
           "groups": {"b0": {
               "ln1": {"scale": lw["ln1"]}, "ln2": {"scale": lw["ln2"]},
               "mixer": attention_params(lw, m),
               "ffn": {k: lw[k] for k in ("router", "w_gate", "w_up",
                                          "w_down")}}}}
    if "unembed" in canon:
        out["unembed"] = {"table": canon["unembed"]}
    return out
