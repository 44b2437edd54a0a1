"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's op intervals / window), averaged over the
chips of the cell."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else tr.idle_share * 100
