"""Share of the traced window in which a collective (all-to-all,
all-reduce, all-gather, reduce-scatter, collective-permute; an async pair
from its start to its done) runs on a chip and no other operation does, in
%, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else tr.collective_exposed_share * 100
