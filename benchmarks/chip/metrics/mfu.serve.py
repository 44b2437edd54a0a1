"""Model FLOP utilization of serving, in %: the forward operations of the
tokens processed in the window (each admitted prompt through prefill with
one row of logits, each decoded token with attention over its real
context and one row of logits), over window x chips x peak bf16 FLOP/s."""

from chipbench import flops


def read(run):
    steps = run.get("steps")
    if not steps or run.get("peaks") is None:
        return None
    m, P = run["dims"], run["prompt_tokens"]
    work = sum(s.admitted * flops.prefill_flops(m, P) +
               sum(flops.decode_flops(m, k) for k in s.decode_keys)
               for s in steps)
    return work / (run["window_s"] * run["chips"] *
                   run["peaks"]["bf16_flops_per_s"]) * 100
