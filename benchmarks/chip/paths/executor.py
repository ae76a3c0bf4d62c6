"""Host path: ``make_source(ScheduleSpec)`` feeding ``SelfSchedulingExecutor``.

A frame builds a fresh source, and ``pes`` worker threads self-schedule its
chunks.  A worker runs the device body on its chunk, one call per tile, and
waits for the result before it claims the next chunk, as a PE of the paper
finishes its chunk first.  The frame ends when every chunk's result is ready.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

SPANS = ("make_source", "claim", "body", "block")


class _TracedSource:
    """The source, with a ``claim`` span around each claim."""

    def __init__(self, source):
        self._source = source

    def claim(self, worker: int = 0):
        import jax

        with jax.profiler.TraceAnnotation("claim"):
            return self._source.claim(worker)

    def __getattr__(self, name):
        return getattr(self._source, name)


class Frame:
    def __init__(self, view, records, tiles, source_build_s, n):
        self.view, self.records, self.source_build_s = view, records, source_build_s
        self._tiles = tiles
        self.complete = sum(r.hi - r.lo for r in records) == n

    def chunks(self):
        """(step, lo, size) of every chunk the source handed out."""
        return [(r.step, r.lo, r.hi - r.lo) for r in self.records]

    def tiles(self):
        """(lo, size, results) of every body call, on the host."""
        return [(lo, size, np.asarray(out).reshape(-1)) for lo, size, out in self._tiles]


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
        import jax

        from repro.core.executor import SelfSchedulingExecutor
        from repro.core.source import make_source

        span = jax.profiler.TraceAnnotation if traced else (lambda _: contextlib.nullcontext())
        tile, step, tiles = self.tile, self.tile_size, []
        view_dev = jax.device_put(view, self.device)

        def fn(lo, hi):
            for a in range(lo, hi, step):
                size = min(step, hi - a)
                with span("body"):
                    out = tile(np.array([a, size], np.int32), view_dev)
                with span("block"):
                    out.block_until_ready()
                tiles.append((a, size, out))

        t0 = time.perf_counter()
        with span("make_source"):
            source = make_source(self.spec)
        build_s = time.perf_counter() - t0
        ex = SelfSchedulingExecutor(self.spec.technique, self.spec.to_params(), self.spec.mode,
                                    source=_TracedSource(source) if traced else source,
                                    scenario=self.scenario)
        ex.run(fn, n_workers=self.workers)
        return Frame(view, ex.records, tiles, build_s, self.n)
