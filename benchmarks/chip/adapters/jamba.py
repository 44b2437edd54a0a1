"""The program's view of a Jamba configuration: its ``ArchConfig`` (one
period of the layer pattern, starting at the attention layer; the MoE
capacity from the configuration's ``run`` settings) and its parameter
tree, filled from the benchmark's canonical weights."""

from __future__ import annotations

from repro.configs.base import ArchConfig, MoEConfig, SSMConfig

from references import jamba as ref

MIXER = {"attention": "attn", "mamba": "mamba"}


def _period(conf):
    kinds = ref.layer_kinds(conf)
    p = conf["attn_layer_period"]
    if kinds[0][0] != "attention" or len(kinds) % p or \
            p % conf["expert_layer_period"]:
        raise ValueError("the program runs whole periods of the layer "
                         "pattern that start at the attention layer")
    return kinds[:p]


def arch_config(conf) -> ArchConfig:
    m = ref.dims(conf)
    period = _period(conf)
    return ArchConfig(
        name=conf["name"], family="hybrid", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["hd"], d_ff=m["ff"],
        vocab_size=m["V"], pattern=tuple(MIXER[mix] for mix, _ in period),
        moe=MoEConfig(n_experts=m["E"], top_k=m["top_k"],
                      capacity_factor=float(conf["run"]["capacity_factor"]),
                      normalize_gates=False),
        moe_pattern=tuple(f == "moe" for _, f in period),
        ssm=SSMConfig(state_dim=m["N"], expand=conf["mamba_expand"],
                      conv_dim=m["conv"], dt_rank=m["dtr"], inner_norms=True),
        rope=False, tie_embeddings=bool(conf["tie_word_embeddings"]))


def program_params(canon, conf):
    m = ref.dims(conf)
    period = _period(conf)
    P, lw = len(period), canon["layers"]
    G = m["L"] // P

    def stack(name, pos, kind):
        """``name``'s rows for pattern position ``pos`` of every group:
        each period holds the same kinds, so they are evenly spaced."""
        per = sum(kind in k for k in period)
        first = sum(kind in k for k in period[:pos])
        return lw[name][first:first + G * per:per]

    groups = {}
    for pos, (mix, ffn) in enumerate(period):
        b = {"ln1": {"scale": lw["ln1"][pos::P]},
             "ln2": {"scale": lw["ln2"][pos::P]}}
        if mix == "attention":
            def a(n):
                return stack("attn_" + n, pos, mix)
            L, d, H, K, hd = G, m["d"], m["H"], m["K"], m["hd"]
            b["mixer"] = {"w_q": a("wq").reshape(L, d, H, hd),
                          "w_k": a("wk").reshape(L, d, K, hd),
                          "w_v": a("wv").reshape(L, d, K, hd),
                          "w_o": a("wo").reshape(L, H, hd, d)}
        else:       # the canonical Mamba leaves carry the program's names
            b["mixer"] = {k[6:]: stack(k, pos, mix) for k in lw
                          if k.startswith("mamba_")}
        b["ffn"] = {k[len(ffn) + 1:]: stack(k, pos, ffn) for k in lw
                    if k.startswith(ffn + "_")}
        if ffn == "moe":        # the experts' gate and up are (out, in)
            for k in ("w_gate", "w_up"):
                b["ffn"][k] = b["ffn"][k].swapaxes(-1, -2)
        groups[f"b{pos}"] = b
    out = {"embed": {"table": canon["embed"]},
           "final_norm": {"scale": canon["final_norm"]}, "groups": groups}
    if "unembed" in canon:
        out["unembed"] = {"table": canon["unembed"]}
    return out
