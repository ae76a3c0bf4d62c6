"""Claim: mean time a claim waited for its source's lock, in us, over the
claims of the traced frames whose source reports it (``wait_s`` of the
executor's records, the program's ``lock_wait`` span).  Only frames run with
the program's tracing on record it; lock-free sources record 0."""


def read(run):
    waits = [r.wait_s for f in run.frames for r in getattr(f, "records", ())
             if getattr(r, "wait_s", None) is not None]
    return 1e6 * sum(waits) / len(waits) if waits else None
