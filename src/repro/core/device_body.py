"""The device-call layer: a chunk ``[lo, hi)`` as calls of a device body.

``DeviceBody(tile, tile_size, *args)`` is the ``fn(lo, hi)`` that
``SelfSchedulingExecutor.run`` calls once per claimed chunk.  For each tile
of ``tile_size`` iterations of the chunk, in order, it calls ``tile`` with
the tile's ``(lo, size)`` as a host ``np.int32`` pair and ``args``, waits
for the result (``block_until_ready``) before the next tile, as a PE of the
paper finishes its chunk before it claims the next, and appends
``(lo, size, out)`` to ``results``.

The switch (``tracing.enabled()``) is read once, when the body is built:

* off, the loop opens no span and reads no clock;
* on, each call runs under a ``dispatch`` span and each wait under a
  ``block`` span, and ``stamps`` gets one ``(dispatch_s, block_s)`` pair a
  call on the host's wall clock (``time.perf_counter``).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import tracing

__all__ = ["DeviceBody"]


class DeviceBody:
    def __init__(self, tile, tile_size: int, *args):
        self.tile, self.tile_size, self.args = tile, tile_size, args
        self.results = []  # (lo, size, out) of every call
        self.stamps = []  # (dispatch_s, block_s) of every call, with tracing on
        self._traced = tracing.enabled()

    def __call__(self, lo: int, hi: int) -> None:
        tile, step, args, results = self.tile, self.tile_size, self.args, self.results
        if not self._traced:
            for a in range(lo, hi, step):
                size = min(step, hi - a)
                out = tile(np.array([a, size], np.int32), *args)
                out.block_until_ready()
                results.append((a, size, out))
            return
        span, clock, stamps = tracing.span, time.perf_counter, self.stamps
        for a in range(lo, hi, step):
            size = min(step, hi - a)
            with span("dispatch"):
                t0 = clock()
                out = tile(np.array([a, size], np.int32), *args)
                t1 = clock()
            with span("block"):
                out.block_until_ready()
                t2 = clock()
            results.append((a, size, out))
            stamps.append((t1 - t0, t2 - t1))
