"""Mean wall time of the engine steps that admitted nothing (a decode
step of the whole batch, with the host's share of it), in ms.  Summed over
every such step of the window, then divided by their count."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.get("steps", ()) if not s.admitted]
    return sum(ts) / len(ts) * 1e3 if ts else None
