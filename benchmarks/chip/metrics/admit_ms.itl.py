"""Time an admission adds to an engine step, in ms: the wall time of the
steps that admitted, less a decode step each (``decode_step_ms.itl``),
over the requests they admitted.  Covers the serial prefill, the cache
write into the block pools and the first-token read-back."""


def read(run):
    steps = run.get("steps", ())
    dec = [s.t1 - s.t0 for s in steps if not s.admitted]
    adm = [s for s in steps if s.admitted]
    if not dec or not adm:
        return None
    per_decode = sum(dec) / len(dec)
    extra = sum(s.t1 - s.t0 for s in adm) - len(adm) * per_decode
    return extra / sum(s.admitted for s in adm) * 1e3
