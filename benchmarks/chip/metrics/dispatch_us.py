"""Dispatch and execute: mean time of one device-body call, from the call
to its return with the result not yet waited for, in us, over the calls of
the traced frames (the adapter's ``dispatch`` stamps, taken only with the
program's tracing on).  Host-path frames only."""


def read(run):
    calls = [d for f in run.frames for d, _ in getattr(f, "stamps", ())]
    return 1e6 * sum(calls) / len(calls) if calls else None
