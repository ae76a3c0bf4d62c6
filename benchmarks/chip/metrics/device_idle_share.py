"""Device: the share of the traced window in which no operation ran on the
device, 1 - busy / window, averaged over the cell's chips.  Busy time is
the union of the device's operation intervals in the profiler trace."""


def read(run):
    busy = run.trace["busy_s"]
    if not busy or not any(busy):
        return None
    return 1.0 - sum(busy) / len(busy) / run.trace["window_s"]
