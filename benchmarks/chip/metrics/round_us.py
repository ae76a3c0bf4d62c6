"""SPMD rounds: the chips' summed device busy time in the traced window over
their live rounds in the traced frames, in us: the device's cost of one
chunk, the counterpart of the host cells' ``chunk_exec_us``.  Summed, not
paired chip by chip: ``busy_s`` is in device order and a frame's steps in
mesh order, which differ (``jax.make_mesh`` orders a v5e 2x2 as devices 0,
1, 3, 2).  Cells of two or more chips."""


def read(run):
    busy = run.trace["busy_s"]
    rounds = sum(len(frame.chunks()) for frame in run.frames)
    if run.chips < 2 or not any(busy) or not rounds:
        return None
    return 1e6 * sum(busy) / rounds
