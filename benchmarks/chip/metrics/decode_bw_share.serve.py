"""Share of peak HBM bandwidth that the least bytes of the decode steps
reach, in %: the weights read once, each decoded token's live KV at its
real depth and its new KV written, summed over the steps that admitted
nothing, over their wall time and the chip's peak bytes/s."""


def read(run):
    steps = [s for s in run.get("steps", ()) if not s.admitted]
    if not steps or run.get("peaks") is None:
        return None
    kvb = run["kv_bytes_per_token"]
    nbytes = sum(run["weight_bytes"] + kvb * sum(s.decode_keys)
                 for s in steps)
    secs = sum(s.t1 - s.t0 for s in steps)
    return nbytes / secs / run["peaks"]["hbm_bytes_per_s"] * 100
