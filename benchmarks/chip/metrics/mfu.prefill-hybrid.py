"""Model FLOP utilization of the sharded prefill of a hybrid Mamba /
attention / MoE stack, in %: the forward operations the prompts require
(``chipbench/hybrid_flops.py``: the Mamba projections, convolution and
scan, causal attention, the top-k experts per token and not capacity
slots, one row of logits per prompt), over the summed wall time of the
window's steps x chips x peak bf16 FLOP/s."""

from chipbench import hybrid_flops


def read(run):
    steps = run.get("prefill_steps")
    if not steps or run.get("peaks") is None or "kinds" not in run["dims"]:
        return None
    work = len(steps) * run["batch"] * hybrid_flops.prefill_flops(
        run["dims"], run["prompt_tokens"])
    span = sum(t1 - t0 for t0, t1 in steps)
    return work / (span * run["chips"] *
                   run["peaks"]["bf16_flops_per_s"]) * 100
