"""Dispatch and execute: mean time a device-body call's result is waited
for (``block_until_ready``), in us, over the calls of the traced frames (the
adapter's ``block`` stamps, taken only with the program's tracing on).
Host-path frames only."""


def read(run):
    waits = [b for f in run.frames for _, b in getattr(f, "stamps", ())]
    return 1e6 * sum(waits) / len(waits) if waits else None
