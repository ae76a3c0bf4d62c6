"""SPMD rounds: the busiest chip's device busy time over the chips' mean, in
the traced window.  1 is perfect balance.  Cells of two or more chips."""


def read(run):
    busy = run.trace["busy_s"]
    if len(busy) < 2 or not any(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
