"""Claim: mean time from a worker's previous chunk done to its next chunk
claimed, in us, over the chunks of the traced frames.  It holds the claim
call, its lock wait and any injected calculation delay.  Read from the
executor's records (``t_claim``, ``t_done``); host-path frames only."""


def read(run):
    gaps = []
    for frame in run.frames:
        last = {}
        for r in sorted(getattr(frame, "records", ()), key=lambda r: r.t_claim):
            if r.worker in last:
                gaps.append(r.t_claim - last[r.worker])
            last[r.worker] = r.t_done
    return 1e6 * sum(gaps) / len(gaps) if gaps else None
