"""Host-level self-scheduling executor: real threads, a real shared counter.

This is the working analogue of LB4MPI inside one address space: worker
threads self-schedule chunks of an iteration space and execute a user
function.  Since the ChunkSource redesign the executor owns **no scheduling
logic at all** — it drives whatever ``ChunkSource`` backend the mode selects
(see core/source.py):

* ``dca``      -> ``StaticSource``: lock-free fetch-and-add against the
  precomputed closed-form schedule (the paper's DCA).
* ``cca``      -> ``CriticalSectionSource``: the recursion runs while holding
  the queue lock (the paper's baseline).
* ``adaptive`` -> ``AdaptiveSource``: AWF-B/C/D/E and AF under DCA semantics
  via epoch-published snapshots.  ``mode="dca"`` with a feedback technique
  promotes here (with a warning) instead of silently synchronizing.
* ``dca_sync`` -> the paper's explicit AF-under-DCA fallback (calculation
  pulled back under the lock).
* ``technique="auto"`` -> ``SelectingSource`` (select/simas.py): the SimAS
  selector picks the technique online and re-picks it at chunk boundaries
  as claim/report feedback accumulates.

``scenario=`` (a ``PerturbationScenario``, select/scenarios.py) drives the
run through ``runtime.inject.ScenarioInjector``: the scenario's calculation
delay is injected per claim (serialized inside the lock for CCA-style
sources, concurrent on the claiming worker for DCA-style sources — exactly
the simulators' split) and its per-PE speed profiles stretch each chunk's
real execution, sampled at chunk start on a shared run clock.  The legacy
``calc_delay_s`` scalar is kept as the constant-scenario alias (same
behaviour as before the injection layer existed).

With ``core/tracing.py`` switched on when ``run`` starts, the workers run a
loop that opens ``claim`` and ``report`` spans and records each chunk's lock
wait and the worker thread's CPU time; otherwise they open no span.

Used by: data/scheduler.py (document->rank assignment), runtime/straggler.py
(microbatch claims), examples/slowdown_reproduction.py, and the cross-engine
conformance suite (tests/test_conformance.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from . import tracing
from .source import ChunkSource, resolve_mode, _source_for
from .techniques import DLSParams, auto_technique, get_technique

__all__ = ["SelfSchedulingExecutor", "ChunkRecord"]


def _resolve_scenario(scenario, calc_delay_s: float, P: int):
    """Normalize the (scenario, legacy calc_delay_s) pair for an executor.

    Returns ``(scenario, delay_calc_s, injector)``: normalization goes
    through the simulators' single ``normalize_scenario`` helper (the legacy
    scalar becomes a constant scenario — the paper's original perturbation,
    aliased rather than a second code path); a ``ScenarioInjector`` is built
    only when the scenario actually perturbs speeds, carries faults, or
    models the network — a uniform static profile *is* the machine's native
    pace under relative speeds, so stretching would only add overhead.
    """
    from .simulator import normalize_scenario

    scenario = normalize_scenario(
        scenario, P, delay_calc_s=calc_delay_s, warn=False,
        on_delay_conflict="error",
    )
    if scenario is None:
        return None, 0.0, None
    injector = None
    # faults force an injector even under uniform static speeds (the fault
    # table and fired flags live in the injector's shared block); a network
    # model does too (the injector owns the per-claim transport pricing)
    if (
        getattr(scenario, "has_faults", False)
        or getattr(scenario, "has_network", False)
        or not (scenario.static and np.ptp(scenario.base_speeds()) == 0.0)
    ):
        from repro.runtime.inject import ScenarioInjector  # runtime imports core

        injector = ScenarioInjector(scenario)
    return scenario, float(scenario.delay_calc_s), injector


class ChunkRecord:
    """One executed chunk.  ``t_req``: the worker asked for it; ``t_claim``:
    it had it, delays paid; ``t_done``: ``fn`` returned (``perf_counter``
    seconds).  With tracing on, ``wait_s`` is the claim's lock wait (where
    the source knows it) and ``cpu_s`` the worker thread's CPU time in
    ``fn``; otherwise both are ``None``."""

    __slots__ = ("step", "lo", "hi", "worker", "t_claim", "t_done", "t_req", "wait_s", "cpu_s")

    def __init__(self, step, lo, hi, worker, t_claim, t_done, t_req=None, wait_s=None,
                 cpu_s=None):
        self.step, self.lo, self.hi = step, lo, hi
        self.worker, self.t_claim, self.t_done = worker, t_claim, t_done
        self.t_req, self.wait_s, self.cpu_s = t_req, wait_s, cpu_s

    def __repr__(self):
        return f"ChunkRecord(step={self.step}, [{self.lo},{self.hi}), w={self.worker})"


class SelfSchedulingExecutor:
    """Self-schedule ``fn(lo, hi)`` over [0, N) across ``n_workers`` threads."""

    def __init__(
        self,
        technique: str,
        params: DLSParams,
        mode: str = "dca",
        calc_delay_s: float = 0.0,
        source: Optional[ChunkSource] = None,
        scenario=None,
    ):
        # always a Technique object — selector mode gets the "auto" sentinel,
        # so callers reading .name / .requires_feedback never see a bare str
        self.technique = auto_technique() if technique == "auto" else get_technique(technique)
        self.params = params
        if scenario is not None and getattr(scenario, "has_faults", False):
            # a crash fault SIGKILLs its worker's *process* — under threads
            # that is the whole executor; fault scenarios need process
            # workers (repro.dist.DistributedExecutor)
            raise ValueError(
                "fault scenarios require process-level workers; use "
                f"repro.dist.DistributedExecutor for {scenario.name!r}"
            )
        self.scenario, self.calc_delay_s, self._injector = _resolve_scenario(
            scenario, calc_delay_s, params.P
        )
        # under a network model, serialized claims extend the coordinator's
        # critical section by the reply's port serialization (the simulators'
        # ``service + serialization_s``); the concurrent wire legs are paid
        # per claim in the worker loop via ``injector.claim_delay``
        coord_extra = (
            self._injector.coordinator_service_extra()
            if self._injector is not None
            else 0.0
        )
        if source is not None:
            serial_delay = self.calc_delay_s + (coord_extra if source.serialized else 0.0)
            if serial_delay and source.serialized:
                # the serialized delay belongs inside the source's own
                # critical section, not on the claiming worker
                from repro.runtime.inject import inject_source  # runtime imports core

                source = inject_source(source, serial_delay)
            self.source = source
            self.mode = "custom"
        else:
            self.mode, _ = resolve_mode(technique, mode)
            build_delay = self.calc_delay_s
            if coord_extra and self.mode in ("cca", "dca_sync"):
                build_delay += coord_extra
            self.source = _source_for(
                technique, params, mode, calc_delay_s=build_delay
            )
        self.records: List[ChunkRecord] = []
        self._records_lock = threading.Lock()

    def close(self):
        """Release the scenario injector's shared block (no-op without one)."""
        if self._injector is not None:
            self._injector.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- chunk claiming ------------------------------------------------------

    def _loop_delay(self) -> float:
        """The per-claim delay the worker loop owes: zero for serialized
        sources (they sleep inside their critical section) and for sources
        that inject their own (``InjectedSource`` — paying it here too would
        double the delay)."""
        src = self.source
        if src.serialized or getattr(src, "injects_delay", False):
            return 0.0
        return self.calc_delay_s

    def _claim(self, worker: int = 0) -> Optional[Tuple[int, int, int]]:
        """Legacy-shaped claim: (step, lo, hi) or None.  Kept for callers of
        the pre-ChunkSource executor; new code should use ``source.claim``."""
        c = self.source.claim(worker)
        if c is None:
            return None
        delay = self._loop_delay()
        if delay:
            time.sleep(delay)  # injected slowdown (concurrent)
        return c.step, c.lo, c.hi

    # -- execution -----------------------------------------------------------

    def run(self, fn: Callable[[int, int], None], n_workers: int) -> float:
        """Execute; returns wall-clock parallel time (the paper's T_loop^par)."""
        t0 = time.perf_counter()
        injector = self._injector
        if injector is not None:
            injector.start()  # stamp the shared run clock before workers start

        # per-claim transport (network model): the wire legs are concurrent
        # on the claiming worker, sampled at its current link factor; sources
        # that inject their own delay (make_source-wrapped) already price the
        # claim transport, so paying it here too would double-charge
        net_claims = (
            injector is not None
            and injector.has_network
            and not getattr(self.source, "injects_delay", False)
        )
        serialized = self.source.serialized
        amortized = bool(getattr(self.source, "amortizes_network", False))
        delay = self._loop_delay()
        pays = net_claims or bool(delay)

        def pay(wid: int):
            if net_claims:
                nd = injector.claim_delay(wid, serialized, amortized)
                if nd:
                    time.sleep(nd)  # claim transport, concurrent wire legs
            if delay:
                time.sleep(delay)  # calculation slowdown, concurrent (DCA)

        def worker(wid: int):
            source = self.source
            # per-chunk speed stretching, sampled at chunk start (scenario)
            run_fn = injector.bind(fn, wid) if injector is not None else fn
            while True:
                t_req = time.perf_counter()
                chunk = source.claim(wid)
                if chunk is None:
                    return
                if pays:
                    pay(wid)
                t_claim = time.perf_counter()
                run_fn(chunk.lo, chunk.hi)
                t_done = time.perf_counter()
                source.report(chunk, t_done - t_claim, overhead=t_claim - t_req)
                with self._records_lock:
                    self.records.append(
                        ChunkRecord(chunk.step, chunk.lo, chunk.hi, wid, t_claim, t_done, t_req)
                    )

        def traced_worker(wid: int):
            # the same loop with spans (claim, report) and the thread's CPU
            # time in fn; kept apart so that the untraced loop pays nothing
            source = self.source
            run_fn = injector.bind(fn, wid) if injector is not None else fn
            span, cpu = tracing.span, time.thread_time
            while True:
                t_req = time.perf_counter()
                with span("claim"):
                    chunk, wait_s = source.claim_timed(wid)
                    if chunk is None:
                        return
                    if pays:
                        pay(wid)
                t_claim = time.perf_counter()
                c0 = cpu()
                run_fn(chunk.lo, chunk.hi)
                cpu_s = cpu() - c0
                t_done = time.perf_counter()
                with span("report"):
                    source.report(chunk, t_done - t_claim, overhead=t_claim - t_req)
                    record = ChunkRecord(chunk.step, chunk.lo, chunk.hi, wid, t_claim, t_done,
                                         t_req, wait_s, cpu_s)
                    with self._records_lock:
                        self.records.append(record)

        loop = traced_worker if tracing.enabled() else worker
        threads = [threading.Thread(target=loop, args=(w,)) for w in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    # -- verification ---------------------------------------------------------

    def executed_ranges(self) -> np.ndarray:
        """Sorted (lo, hi) pairs; tests assert exact [0, N) coverage."""
        with self._records_lock:
            pairs = sorted((r.lo, r.hi) for r in self.records)
        return np.asarray(pairs, dtype=np.int64).reshape(-1, 2)

    def chunk_size_sequence(self) -> np.ndarray:
        """Chunk sizes in scheduling-step order — for non-feedback techniques
        this sequence is execution-independent and must match the simulators'
        ``chunk_sizes`` exactly (the conformance suite's shared contract)."""
        with self._records_lock:
            pairs = sorted((r.step, r.hi - r.lo) for r in self.records)
        return np.asarray([s for _, s in pairs], dtype=np.int64)
