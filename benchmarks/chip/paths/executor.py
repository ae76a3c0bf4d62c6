"""Host path: ``make_source(ScheduleSpec)`` feeding ``SelfSchedulingExecutor``.

A frame builds a fresh source, and ``pes`` worker threads self-schedule its
chunks.  A worker hands its chunk to the device-body adapter, which runs the
device body on it one call per tile and waits for each result, as a PE of
the paper finishes its chunk before it claims the next.  The frame ends when
every chunk's result is ready.

A traced frame runs under the program's tracing switch: the executor, the
sources and the adapter open their spans and stamp their records.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from device_body import DeviceBody
from repro.core import tracing

SPANS = tuple(dict.fromkeys(("make_source", "dispatch", "block") + tracing.SPANS))


class Frame:
    def __init__(self, view, records, body, source_build_s, n):
        self.view, self.records, self.source_build_s = view, records, source_build_s
        self._results = body.results
        self.stamps = body.stamps
        self.complete = sum(r.hi - r.lo for r in records) == n

    def chunks(self):
        """(step, lo, size) of every chunk the source handed out."""
        return [(r.step, r.lo, r.hi - r.lo) for r in self.records]

    def tiles(self):
        """(lo, size, results) of every body call, on the host."""
        return [(lo, size, np.asarray(out).reshape(-1)) for lo, size, out in self._results]


class Runner:
    spans = SPANS

    def __init__(self, cfg, traffic, devices, tile, tile_size, schedule):
        from repro.core.source import ScheduleSpec
        from repro.select.scenarios import PerturbationScenario

        self.n, self.workers = cfg["N"], cfg["pes"]
        self.device = devices[0]
        self.tile, self.tile_size = tile, tile_size
        delay = traffic["delay_calc_s"]
        self.scenario = (PerturbationScenario.constant(cfg["P"], delay_calc_s=delay)
                         if delay else None)
        self.spec = ScheduleSpec(traffic["technique"], N=cfg["N"], P=cfg["P"],
                                 mode=traffic["mode"], scenario=self.scenario)

    def frame(self, view, traced: bool = False) -> Frame:
        with tracing.on() if traced else contextlib.nullcontext():
            return self._frame(view)

    def _frame(self, view) -> Frame:
        import jax

        from repro.core.executor import SelfSchedulingExecutor
        from repro.core.source import make_source

        body = DeviceBody(self.tile, self.tile_size, jax.device_put(view, self.device))
        t0 = time.perf_counter()
        with tracing.span("make_source") if tracing.enabled() else contextlib.nullcontext():
            source = make_source(self.spec)
        build_s = time.perf_counter() - t0
        ex = SelfSchedulingExecutor(self.spec.technique, self.spec.to_params(), self.spec.mode,
                                    source=source, scenario=self.scenario)
        ex.run(body, n_workers=self.workers)
        return Frame(view, ex.records, body, build_s, self.n)
