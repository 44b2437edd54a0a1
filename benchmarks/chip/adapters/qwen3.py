"""The program's view of a Qwen3 configuration: its ``ArchConfig`` and its
parameter tree, filled from the benchmark's canonical weights."""

from __future__ import annotations

from repro.configs.base import ArchConfig

from references import qwen3 as ref


def arch_config(conf) -> ArchConfig:
    m = ref.dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["V"], qk_norm=True, rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]))


def attention_params(lw, m):
    L, d, H, K, hd = m["L"], m["d"], m["H"], m["K"], m["hd"]
    return {"w_q": lw["wq"].reshape(L, d, H, hd),
            "w_k": lw["wk"].reshape(L, d, K, hd),
            "w_v": lw["wv"].reshape(L, d, K, hd),
            "w_o": lw["wo"].reshape(L, H, hd, d)}


def program_params(canon, conf):
    m = ref.dims(conf)
    lw = canon["layers"]
    mixer = attention_params(lw, m)
    mixer.update(q_scale=lw["q_norm"], k_scale=lw["k_norm"])
    out = {"embed": {"table": canon["embed"]},
           "final_norm": {"scale": canon["final_norm"]},
           "groups": {"b0": {
               "ln1": {"scale": lw["ln1"]}, "ln2": {"scale": lw["ln2"]},
               "mixer": mixer,
               "ffn": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}}}
    if "unembed" in canon:
        out["unembed"] = {"table": canon["unembed"]}
    return out
