"""Model FLOP utilization of the sharded prefill, in %: the forward
operations the prompts require (top-k experts per token, not capacity
slots; causal attention; one row of logits per prompt), over the summed
wall time of the window's steps x chips x peak bf16 FLOP/s.  The steps
run back to back, so their sum is the window less the loop's own turns
(and, in a traced run, less the profiler's start)."""

from chipbench import flops


def read(run):
    steps = run.get("prefill_steps")
    if not steps or run.get("peaks") is None:
        return None
    work = len(steps) * run["batch"] * flops.prefill_flops(
        run["dims"], run["prompt_tokens"])
    span = sum(t1 - t0 for t0, t1 in steps)
    return work / (span * run["chips"] *
                   run["peaks"]["bf16_flops_per_s"]) * 100
