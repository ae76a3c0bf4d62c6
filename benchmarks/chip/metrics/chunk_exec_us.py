"""Dispatch and execute: mean time from a chunk claimed to its results
ready on the host, in us, over the chunks of the traced frames.  Read from
the executor's records (``t_done - t_claim``); host-path frames only."""


def read(run):
    spans = [r.t_done - r.t_claim for f in run.frames for r in getattr(f, "records", ())]
    return 1e6 * sum(spans) / len(spans) if spans else None
