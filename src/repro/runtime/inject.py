"""Scenario injection: PerturbationScenario driving *real* execution.

The simulators accept any ``PerturbationScenario`` (select/scenarios.py)
through ``SimConfig.scenario``; the real executors historically only knew the
paper's single scalar ``calc_delay_s``.  ``ScenarioInjector`` closes that
gap: it publishes a scenario's padded per-PE speed tables plus a shared run
clock so that worker *threads and processes* sample the same profiles the
simulators read, and stretches real chunk execution to match.

Semantics, chosen to mirror the simulators exactly (DESIGN.md Sec. 11):

* **Speed profiles -> per-chunk stretching.**  A worker samples its PE's
  relative speed once, at chunk start, on the shared run clock — the
  simulators' chunk-granular sampling (``speed_at(pe, done)``) — and holds
  it for the chunk: the chunk's measured execution time ``e`` is stretched
  to ``e * s_max / s`` by sleeping the difference after the workload ran.
  ``s_max`` (the scenario's fastest speed anywhere) anchors the
  normalization: real hardware cannot run *faster* than unperturbed, so the
  fastest profile speed maps to the machine's native pace and everything
  else is a slowdown — relative speeds, which is all the scenarios encode.
* **Calculation delay -> per-claim delay.**  For DCA-style sources
  (``serialized == False``) the delay runs on the claiming worker,
  concurrently across workers (``InjectedSource``); for CCA-style sources
  it belongs *inside* the critical section, which the sources themselves
  implement (``CriticalSectionSource.calc_delay_s``; the foreman applies it
  in its serve loop) — the injector only configures it.
* **One clock, every placement.**  The profile tables, the scenario's
  calculation delay, and the run-clock origin live in one
  ``multiprocessing.shared_memory`` block (dist/shm.py primitives).
  ``start()`` stamps ``time.monotonic()`` — CLOCK_MONOTONIC, whose epoch is
  system-wide — into the block; a pickled injector re-attaches by segment
  name, so spawned ``repro.dist`` workers sample with two array reads and
  no IPC, exactly like a thread.

Used by: core/executor.py and dist/executor.py (``scenario=``),
core/source.py (``ScheduleSpec.scenario`` via ``make_source``),
examples/slowdown_reproduction.py (``--scenario``), and the cross-engine
conformance suite (tests/test_conformance.py).
"""

from __future__ import annotations

import os
import signal
import time
from typing import Callable, Optional

from repro.core.source import Chunk, ChunkSource

__all__ = ["ScenarioInjector", "InjectedSource", "inject_source"]


# shared block layout (byte offsets):
#   int64   [0]        t0_ns   — run-clock origin (time.monotonic_ns), 0 == not started
#   float64 [8]        delay_calc_s
#   float64 [16]       s_max   — normalization anchor (fastest table speed)
#   float64 [24 ..]    times   [P, kmax]      (+inf padded)
#   float64 [.. ..]    speeds  [P, kmax + 1]  (final value repeated)
#   float64 [.. ..]    faults  [F, 4]         (kind_code, pe, t, duration_s)
#   int64   [.. end]   fired   [F]            (0 = pending, 1 = fired)
_HDR_BYTES = 24

# fault kind codes in the shared table (scenarios' FAULT_KINDS, in order);
# the fired flags live in shm so a *respawned* worker re-attaching to the
# same PE slot sees already-fired faults and does not re-fire them.
_FAULT_CODES = {"crash": 1, "hang": 2, "stall": 3, "coordinator_kill": 4}

# stall sleeps in short increments so it can keep stamping its heartbeat
# (a stalled worker is alive-but-slow, not dead); hang never ticks, which
# is precisely what the executor's heartbeat staleness check must catch.
_STALL_TICK_S = 0.05


class ScenarioInjector:
    """Publishes one ``PerturbationScenario`` for sampling from any worker.

    The injector is picklable (it travels in ``Process(args=...)`` like the
    dist sources): the pickle carries the segment name and table shape, and
    ``__setstate__`` re-attaches.  Only the creating process unlinks the
    segment (``close()``); attached copies just drop their mapping.
    """

    def __init__(self, scenario, *, name: Optional[str] = None):
        from repro.dist.shm import create_block

        times, speeds = scenario.padded_tables()
        faults = tuple(getattr(scenario, "faults", ()))
        # network model + link-factor tables: immutable for the whole run, so
        # they ride the pickle (Process args) instead of widening the shared
        # block — only mutable state (run clock, fired flags) needs shm
        self.network = getattr(scenario, "network", None)
        plt = getattr(scenario, "padded_link_tables", None)
        self._ltimes, self._lfactors = plt() if plt is not None else (None, None)
        self.scenario_name = name if name is not None else scenario.name
        self.P = int(times.shape[0])
        self.kmax = int(times.shape[1])
        self.F = len(faults)
        self._owner = True
        self._shm = create_block(
            _HDR_BYTES
            + 8 * (self.P * self.kmax + self.P * (self.kmax + 1))
            + 8 * (4 * self.F + self.F)
        )
        self._map_views()
        self._vals[0] = float(scenario.delay_calc_s)
        self._vals[1] = scenario.max_speed
        self._times[:] = times
        self._speeds[:] = speeds
        for i, f in enumerate(faults):
            self._faults[i, 0] = _FAULT_CODES[f.kind]
            self._faults[i, 1] = float(f.pe)
            self._faults[i, 2] = float(f.t)
            self._faults[i, 3] = float(f.duration_s)

    def _map_views(self):
        from repro.dist.shm import float64_field, int64_field

        P, kmax, F = self.P, self.kmax, self.F
        self._t0 = int64_field(self._shm, 0, 1)
        self._vals = float64_field(self._shm, 8, 2)
        self._times = float64_field(self._shm, _HDR_BYTES, P * kmax).reshape(P, kmax)
        self._speeds = float64_field(
            self._shm, _HDR_BYTES + 8 * P * kmax, P * (kmax + 1)
        ).reshape(P, kmax + 1)
        off = _HDR_BYTES + 8 * (P * kmax + P * (kmax + 1))
        self._faults = float64_field(self._shm, off, 4 * F).reshape(F, 4)
        self._fired = int64_field(self._shm, off + 8 * 4 * F, F)

    def __repr__(self):
        return (
            f"ScenarioInjector({self.scenario_name!r}, P={self.P}, "
            f"delay={self.delay_calc_s * 1e6:.0f}us, "
            f"{'started' if self.started else 'not started'})"
        )

    # -- the shared run clock --------------------------------------------------

    def start(self, t0_ns: Optional[int] = None) -> None:
        """Stamp the run-clock origin (idempotent per run: executors call it
        at the top of ``run()``, re-stamping on reuse).  Must happen in the
        parent *before* workers fork/spawn so every worker sees it."""
        self._t0[0] = int(time.monotonic_ns() if t0_ns is None else t0_ns)

    @property
    def started(self) -> bool:
        return int(self._t0[0]) != 0

    def now(self) -> float:
        """Seconds since ``start()`` on the shared monotonic clock (0.0
        before the clock is stamped — profiles then read their t=0 window,
        which is also what the simulators do at their first event)."""
        t0 = int(self._t0[0])
        return 0.0 if t0 == 0 else (time.monotonic_ns() - t0) / 1e9

    # -- sampling --------------------------------------------------------------

    @property
    def delay_calc_s(self) -> float:
        return float(self._vals[0])

    def speed(self, worker: int, t: Optional[float] = None) -> float:
        """Relative speed of ``worker``'s PE slot (``worker % P``) at ``t``
        (default: now) — the same padded-table lookup, hence the same
        window-start-inclusive boundary semantics, as the simulators'
        ``speed_at``/``speeds_at``."""
        pe = worker % self.P
        tt = self.now() if t is None else t
        return float(self._speeds[pe, int((self._times[pe] <= tt).sum())])

    def slowdown(self, worker: int) -> float:
        """Stretch factor >= 1 for a chunk starting now: ``s_max / speed``."""
        return float(self._vals[1]) / self.speed(worker)

    # -- network ---------------------------------------------------------------

    @property
    def has_network(self) -> bool:
        return self.network is not None

    def link(self, worker: int, t: Optional[float] = None) -> float:
        """Link latency factor of ``worker``'s PE slot at ``t`` (default:
        now) — same padded-table lookup and boundary semantics as ``speed``,
        against the scenario's link tables instead of its speed tables."""
        if self._ltimes is None:
            return 1.0
        pe = worker % self.P
        tt = self.now() if t is None else t
        return float(self._lfactors[pe, int((self._ltimes[pe] <= tt).sum())])

    def claim_delay(self, worker: int, serialized: bool, amortized: bool = False) -> float:
        """Worker-side (concurrent) share of one claim's modeled transport,
        sampled at the worker's current link factor.  The wire legs scale
        with the link; port serialization does not.

        * ``amortized``  — coarse-batch (tree) sources: one TCP refill
          spread over ``batch_chunks`` board re-serves.
        * ``serialized`` — CCA-style round trip: the request drains the
          worker's own port (concurrent, unscaled) plus both propagation
          legs.  The *reply's* serialization at the master's port is the
          coordinator's cost — see ``coordinator_service_extra``.
        * otherwise      — DCA RMA fetch-and-add: two one-way legs.
        """
        net = self.network
        if net is None:
            return 0.0
        lf = self.link(worker)
        if amortized:
            return net.tree_claim_s * lf
        if serialized:
            return net.serialization_s + 2.0 * net.propagation_s * lf
        return 2.0 * net.rma_oneway_s * lf

    def coordinator_service_extra(self) -> float:
        """Per-claim extension of the coordinator's *serialized* service:
        the reply drains the master's single port before the next claim is
        served.  Folded into a serialized source's ``calc_delay_s`` so it is
        paid inside the critical section, exactly as both simulators extend
        ``service`` by ``serialization_s``."""
        return self.network.serialization_s if self.network is not None else 0.0

    # -- faults ----------------------------------------------------------------

    @property
    def has_faults(self) -> bool:
        return self.F > 0

    def worker_has_faults(self, worker: int) -> bool:
        """Does ``worker``'s PE slot have any crash/hang/stall rows?"""
        pe = worker % self.P
        return any(
            self._faults[i, 0] != _FAULT_CODES["coordinator_kill"]
            and int(self._faults[i, 1]) == pe
            for i in range(self.F)
        )

    def fired(self, idx: int) -> bool:
        return bool(self._fired[idx])

    def mark_fired(self, idx: int) -> None:
        self._fired[idx] = 1

    def due_coordinator_fault(self) -> Optional[int]:
        """Index of an unfired ``coordinator_kill`` whose time has come, or
        None.  Polled parent-side (the executor's chaos thread owns the
        foreman pid); the caller marks it fired *before* killing so a
        restarted coordinator is not immediately re-killed."""
        t = self.now()
        for i in range(self.F):
            if (
                not self._fired[i]
                and self._faults[i, 0] == _FAULT_CODES["coordinator_kill"]
                and self._faults[i, 2] <= t
            ):
                return i
        return None

    def poll_faults(self, worker: int, tick: Optional[Callable[[], None]] = None) -> None:
        """Fire any due worker fault for ``worker``'s PE slot.  Called at
        chunk start (chunk-granular, like speed sampling).  Only the worker
        occupying a PE slot polls that slot's rows, so plain check-then-set
        on the shared fired flag is race-free; the flag persists in shm so a
        respawned replacement does not re-fire the fault.

        * ``crash`` — SIGKILL self (flag set first: the kill is immediate).
        * ``hang``  — sleep forever *without* ticking the heartbeat; only
          the executor's staleness detector ends this worker.
        * ``stall`` — sleep ``duration_s`` in short increments, ticking the
          heartbeat each one, then return and keep working.
        """
        pe = worker % self.P
        t = self.now()
        for i in range(self.F):
            code = int(self._faults[i, 0])
            if (
                self._fired[i]
                or code == _FAULT_CODES["coordinator_kill"]
                or int(self._faults[i, 1]) != pe
                or self._faults[i, 2] > t
            ):
                continue
            self._fired[i] = 1
            if code == _FAULT_CODES["crash"]:
                os.kill(os.getpid(), signal.SIGKILL)
            elif code == _FAULT_CODES["hang"]:
                while True:  # pragma: no cover - ended by SIGTERM/SIGKILL
                    time.sleep(3600.0)
            elif code == _FAULT_CODES["stall"]:
                end = time.monotonic() + float(self._faults[i, 3])
                while (left := end - time.monotonic()) > 0:
                    time.sleep(min(left, _STALL_TICK_S))
                    if tick is not None:
                        tick()

    # -- wrappers --------------------------------------------------------------

    def bind(
        self,
        fn: Callable[[int, int], None],
        worker: int,
        tick: Optional[Callable[[], None]] = None,
    ) -> Callable[[int, int], None]:
        """Per-worker workload wrapper: each ``fn(lo, hi)`` call polls the
        worker's due faults, then samples the worker's slowdown at chunk
        start and stretches the chunk's real execution time by it (picklable
        when ``fn`` and ``tick`` are; executors bind worker-side, where
        ``tick`` is a local heartbeat closure)."""
        wrapped: Callable[[int, int], None] = _StretchedFn(self, fn, worker)
        if self.worker_has_faults(worker):
            wrapped = _FaultyFn(self, wrapped, worker, tick)
        return wrapped

    # -- lifecycle -------------------------------------------------------------

    def close(self):
        """Drop this process's mapping; the creator also unlinks."""
        if self._shm is None:
            return
        self._t0 = self._vals = self._times = self._speeds = None
        self._faults = self._fired = None
        if self._owner:
            from repro.dist.shm import unlink_block

            unlink_block(self._shm)
        else:
            self._shm.close()
        self._shm = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # best-effort; executors call close() explicitly
        try:
            self.close()
        except Exception:  # pragma: no cover
            pass

    # -- pickling (Process args) ----------------------------------------------

    def __getstate__(self):
        if self._shm is None:
            raise ValueError("cannot pickle a closed ScenarioInjector")
        return {
            "name": self._shm.name,
            "P": self.P,
            "kmax": self.kmax,
            "F": self.F,
            "scenario_name": self.scenario_name,
            # immutable for the run → pickled by value, not mapped from shm
            "network": self.network,
            "ltimes": self._ltimes,
            "lfactors": self._lfactors,
        }

    def __setstate__(self, state):
        from repro.dist.shm import attach_block

        self.scenario_name = state["scenario_name"]
        self.P = state["P"]
        self.kmax = state["kmax"]
        self.F = state.get("F", 0)
        self.network = state.get("network")
        self._ltimes = state.get("ltimes")
        self._lfactors = state.get("lfactors")
        self._owner = False
        self._shm = attach_block(state["name"])
        self._map_views()


class _StretchedFn:
    """``fn(lo, hi)`` stretched to the scenario's speed, chunk-granularly.

    The slowdown is sampled once at chunk start (the shared run clock) and
    held: the workload runs at native pace, then the wrapper sleeps the
    stretch remainder — total elapsed becomes ``measured * s_max / s``,
    matching the simulators' ``work / speed`` execution model without
    needing to know the workload's cost model.
    """

    __slots__ = ("injector", "fn", "worker")

    def __init__(self, injector: ScenarioInjector, fn, worker: int):
        self.injector = injector
        self.fn = fn
        self.worker = worker

    def __getstate__(self):
        return (self.injector, self.fn, self.worker)

    def __setstate__(self, state):
        self.injector, self.fn, self.worker = state

    def __call__(self, lo: int, hi: int) -> None:
        stretch = self.injector.slowdown(self.worker)  # sampled at chunk start
        t0 = time.perf_counter()
        self.fn(lo, hi)
        if stretch > 1.0:
            time.sleep((time.perf_counter() - t0) * (stretch - 1.0))


class _FaultyFn:
    """``fn(lo, hi)`` preceded by a fault poll at chunk start.

    A crash fires *before* the chunk executes: the chunk was claimed (and,
    under ``DistributedExecutor``, leased) but produced no record — exactly
    the lost-lease shape the executor's reclamation paths must repair.  The
    wrapper composes over ``_StretchedFn`` so slowdowns and faults stack.
    """

    __slots__ = ("injector", "fn", "worker", "tick")

    def __init__(self, injector: ScenarioInjector, fn, worker: int, tick=None):
        self.injector = injector
        self.fn = fn
        self.worker = worker
        self.tick = tick

    def __getstate__(self):
        return (self.injector, self.fn, self.worker, self.tick)

    def __setstate__(self, state):
        self.injector, self.fn, self.worker, self.tick = state

    def __call__(self, lo: int, hi: int) -> None:
        self.injector.poll_faults(self.worker, self.tick)
        self.fn(lo, hi)


class InjectedSource(ChunkSource):
    """A DCA-style source with the scenario's calculation delay applied on
    the claiming worker — concurrent across workers, like the simulators'
    requesting-PE delay (the fetch-and-add inside ``inner.claim`` stays the
    only serialization).  Everything else forwards to ``inner``; picklable
    when the inner source is (SharedStaticSource travels to dist workers
    wrapped).

    ``injects_delay`` marks the source as owning its delay: the executors'
    worker loops check it so a wrapped source passed together with
    ``scenario=`` pays the delay once, not once in ``claim()`` and once in
    the loop."""

    def __init__(self, inner: ChunkSource, delay_calc_s: float):
        if inner.serialized:
            raise ValueError(
                "InjectedSource models the concurrent (DCA) delay; serialized "
                "sources take calc_delay_s inside their critical section"
            )
        self.inner = inner
        self.delay_calc_s = float(delay_calc_s)

    serialized = False
    injects_delay = True

    def claim(self, worker: int = 0) -> Optional[Chunk]:
        chunk = self.inner.claim(worker)
        if chunk is not None and self.delay_calc_s:
            time.sleep(self.delay_calc_s)  # on the claimer, concurrent
        return chunk

    def claim_timed(self, worker: int = 0):
        chunk, wait_s = self.inner.claim_timed(worker)
        if chunk is not None and self.delay_calc_s:
            time.sleep(self.delay_calc_s)
        return chunk, wait_s

    def report(self, chunk: Chunk, elapsed: float, overhead: float = 0.0) -> None:
        self.inner.report(chunk, elapsed, overhead)

    def drained(self) -> bool:
        return self.inner.drained()

    @property
    def claimed(self) -> int:
        return getattr(self.inner, "claimed", 0)

    def materialize(self):
        mat = getattr(self.inner, "materialize", None)
        if mat is None:
            raise ValueError(
                f"{type(self.inner).__name__} chunks depend on execution; "
                "no static schedule"
            )
        return mat()

    def close(self):
        if hasattr(self.inner, "close"):
            self.inner.close()


def inject_source(source: ChunkSource, delay_calc_s: float) -> ChunkSource:
    """Apply a scenario's calculation delay to an existing source with the
    simulator's placement semantics: inside the critical section for
    serialized (CCA-style) sources, concurrent on the claimer for DCA-style
    ones.  Returns the source unchanged when there is nothing to inject."""
    if not delay_calc_s:
        return source
    if source.serialized:
        if hasattr(source, "calc_delay_s"):
            source.calc_delay_s = float(delay_calc_s)
            return source
        raise ValueError(
            f"{type(source).__name__} is serialized but exposes no "
            "calc_delay_s; build it with the delay instead (source_for / "
            "process_source_for accept calc_delay_s)"
        )
    return InjectedSource(source, delay_calc_s)
