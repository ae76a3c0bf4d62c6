"""Claim: mean time from a worker asking for a chunk to having it, in us,
over the chunks of the traced frames: the source's claim, its lock wait and
any injected delay (``t_claim - t_req`` of the executor's records, the
program's ``claim`` span).  Host-path frames of a program that records
``t_req`` only."""


def read(run):
    spans = [r.t_claim - r.t_req for f in run.frames for r in getattr(f, "records", ())
             if getattr(r, "t_req", None) is not None]
    return 1e6 * sum(spans) / len(spans) if spans else None
