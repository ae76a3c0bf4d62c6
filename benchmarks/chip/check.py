"""The comparison that decides ``correct``: the timed path against the reference.

For each frame of the sample, three numbers, each summed over the sample:

- ``pixels_differing``: pixels whose escape count differs from the plain
  reference's, plus masked lanes (past a call's ``size``) that do not read 0.
  The body and the reference do the same float32 operations in the same
  order, and a v5e computes them bit for bit as numpy does, so the limit is
  0.  The control, the body rounded to bfloat16, differs in about 15,000
  pixels a frame.
- ``iterations_not_once``: iterations of [0, N) that the chunks handed out
  cover no time or more than once.
- ``steps_differing``: scheduling steps whose chunk (offset, size) differs
  from the reference schedule, steps missing, and steps handed out twice or
  beyond it.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"pixels_differing": 0, "iterations_not_once": 0, "steps_differing": 0}


def compare(frames, reference, schedule, n: int) -> dict:
    """``{name: [value, limit]}`` over ``frames``; ``reference(view)`` gives a
    frame's escape counts, ``schedule`` the reference chunk sizes in step
    order."""
    ref_offsets = np.concatenate([[0], np.cumsum(schedule)[:-1]]).astype(np.int64)
    want = {i: (int(o), int(s)) for i, (o, s) in enumerate(zip(ref_offsets, schedule))}
    pixels = cover = steps = 0
    for frame in frames:
        got = np.full(n, -1, np.int64)
        for lo, size, res in frame.tiles():
            got[lo:lo + size] = res[:size]
            pixels += int(np.count_nonzero(res[size:]))
        pixels += int(np.count_nonzero(got != reference(frame.view)))
        times = np.zeros(n, np.int64)
        handed = {}
        for step, lo, size in frame.chunks():
            times[lo:lo + size] += 1
            steps += int(step in handed)
            handed[step] = (lo, size)
        cover += int(np.count_nonzero(times != 1))
        steps += sum(handed.get(i) != chunk for i, chunk in want.items())
        steps += sum(i not in want for i in handed)
    return {name: [value, LIMITS[name]] for name, value in
            (("pixels_differing", pixels), ("iterations_not_once", cover),
             ("steps_differing", steps))}


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
