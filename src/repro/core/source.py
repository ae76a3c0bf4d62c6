"""ChunkSource — the one scheduling API every consumer speaks.

The paper separates chunk *calculation* from chunk *assignment* (DCA); this
module makes "which chunks, from where, under what feedback" a single
pluggable axis instead of a loop re-implemented per consumer.  A source hands
out chunks of the iteration space [0, N):

    claim(worker)          -> Chunk | None     (None == iteration space drained)
    report(chunk, elapsed) -> None             (execution feedback, optional)
    drained()              -> bool             (advisory; claim() is authoritative)

Four backends cover the paper's design space:

* ``StaticSource`` — a precomputed DCA schedule (closed forms, vectorized);
  claims are a lock-free fetch-and-add on the step counter (CPython's
  ``itertools.count`` *is* an atomic fetch-and-add), the chunk itself is a
  table lookup.  The paper's DCA, as a reusable object.
* ``CriticalSectionSource`` — the CCA baseline: a master walks the recursion
  while holding the queue lock.  Feedback techniques (AF, AWF-*) run here in
  their classical synchronized form.
* ``AdaptiveSource`` — adaptive techniques (AWF-B/C/D/E, AF) under **DCA
  semantics** via epoch-published snapshots: the source publishes an
  immutable (epoch, weights/μσ) snapshot; a worker computes its chunk size
  *outside* any lock as a pure function of (snapshot, worker, R) — R being
  an unlocked read of the queue head, used like the paper's shared step
  counter — then performs only a fetch-and-add of that size on the queue
  head.  Every P claims the next claimer republishes the snapshot from the
  timings ``report()`` accumulated — so the calculation stays out of the
  critical section (the paper's DCA property) while the technique still
  reacts to measured worker speeds.  CCA fallback becomes a choice
  (``mode="cca"``), not a silent default.
* ``HierarchicalSource`` — two-level composition: groups claim global chunks
  from an inner source, workers drain per-group local sources built over each
  global chunk (replaces ``HierarchicalExecutor``'s bespoke loop).

A fifth backend lives in ``select/simas.py``: ``SelectingSource``
(``technique="auto"``) wraps a StaticSource behind the SimAS online
selector, re-picking the technique at chunk boundaries from claim/report
feedback.  Cross-process analogues live in ``repro.dist``
(``placement="process"``): ``SharedStaticSource`` claims the same precomputed
tables through ``multiprocessing.shared_memory``, and ``ForemanSource`` puts
the CCA master in a real coordinator process (DESIGN.md Sec. 10).

``ScheduleSpec`` is the declarative config (technique, N, P, mode, min_chunk,
hierarchy levels); ``make_source``/``source_for`` build backends from it.
See DESIGN.md Sec. 8.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import tracing
from .schedule import Schedule, build_schedule_cca, build_schedule_dca
from .techniques import (
    AWFFeedback,
    DLSParams,
    awf_variant,
    get_technique,
)

__all__ = [
    "Chunk",
    "ChunkSource",
    "ScheduleSpec",
    "StaticSource",
    "CriticalSectionSource",
    "AdaptiveSource",
    "HierarchicalSource",
    "AFEstimator",
    "make_source",
    "source_for",
    "resolve_mode",
    "materialize",
    "validate_placement",
    "FeedbackScheduleError",
    "ModeDowngradeWarning",
    "PlacementError",
    "PLACEMENTS",
]


MODES = ("auto", "dca", "cca", "adaptive", "dca_sync")
PLACEMENTS = ("thread", "process", "net")


class PlacementError(ValueError):
    """Unknown or unsupported ``placement``.

    Typed (not a bare ``KeyError``/``AttributeError`` from a dispatch table)
    so config errors fail with the full menu: with three placements a typo
    like ``"processes"`` deserves "here is what exists", not a stack trace
    from the middle of a factory.
    """

    def __init__(self, placement):
        super().__init__(
            f"unknown placement {placement!r}: valid placements are "
            "'thread' (in-process backends), 'process' (shared-memory DCA / "
            "foreman CCA, repro.dist), and 'net' (TCP remote-counter DCA / "
            "network-foreman CCA, repro.net)"
        )
        self.placement = placement


def validate_placement(placement: str, allowed: Tuple[str, ...] = PLACEMENTS) -> str:
    """THE placement-validation path: ``ScheduleSpec`` construction, the
    placement dispatch in ``make_source``, and the executors all raise the
    typed ``PlacementError`` from here.  ``allowed`` narrows the menu for
    consumers that support a subset (the distributed executor runs only
    ``"process"``/``"net"``)."""
    if placement not in PLACEMENTS or placement not in allowed:
        raise PlacementError(placement)
    return placement


class ModeDowngradeWarning(UserWarning):
    """Emitted when a requested calculation mode cannot run as asked and the
    effective mode differs (e.g. ``dca`` for a feedback technique)."""


class FeedbackScheduleError(ValueError):
    """A feedback-driven schedule was asked to do something only closed-form
    schedules can (``materialize()``, chunk-table precomputation).

    Typed so engine fallbacks can catch *exactly* this condition: the fast
    engine reroutes a feedback source to the event engine on this error and
    nothing else — a genuine table-construction bug (any other ValueError)
    propagates instead of disappearing into a slow-but-plausible run."""


class Chunk:
    """One claimed chunk: iteration range [lo, hi) at scheduling step ``step``.

    ``worker`` is the claiming worker id; ``epoch`` is the AdaptiveSource
    epoch whose snapshot sized this chunk (0 elsewhere).  A plain __slots__
    class, not a dataclass: claims are the hot path (BENCH_source_overhead)
    and frozen-dataclass construction costs ~3x a direct init."""

    __slots__ = ("step", "lo", "hi", "worker", "epoch")

    def __init__(self, step: int, lo: int, hi: int, worker: int = 0, epoch: int = 0):
        self.step = step
        self.lo = lo
        self.hi = hi
        self.worker = worker
        self.epoch = epoch

    @property
    def size(self) -> int:
        return self.hi - self.lo

    def __repr__(self):
        return (
            f"Chunk(step={self.step}, [{self.lo},{self.hi}), "
            f"w={self.worker}, e={self.epoch})"
        )

    def __eq__(self, other):
        return (
            isinstance(other, Chunk)
            and (self.step, self.lo, self.hi, self.worker, self.epoch)
            == (other.step, other.lo, other.hi, other.worker, other.epoch)
        )


class ChunkSource:
    """Protocol base (also usable as an ABC for isinstance checks).

    ``serialized`` tells timing models whether claims serialize the chunk
    *calculation* (CCA: yes — the paper's master; DCA-style sources: no —
    only the fetch-and-add serializes)."""

    serialized: bool = False

    def claim(self, worker: int = 0) -> Optional[Chunk]:  # pragma: no cover
        raise NotImplementedError

    def claim_timed(self, worker: int = 0) -> Tuple[Optional[Chunk], Optional[float]]:
        """``claim`` and the seconds it waited for a lock (``None``: not
        known).  Called by the executor's traced loop only."""
        return self.claim(worker), None

    def report(self, chunk: Chunk, elapsed: float, overhead: float = 0.0) -> None:
        """Execution feedback: ``elapsed`` is the chunk's compute time,
        ``overhead`` the scheduling overhead (consumed by AWF-D/E)."""

    def drained(self) -> bool:  # pragma: no cover
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Mode resolution
# ---------------------------------------------------------------------------


def resolve_mode(technique: str, mode: str = "auto") -> Tuple[str, Optional[str]]:
    """Map (technique, requested mode) -> (effective mode, warning | None).

    ``auto`` picks ``dca`` where the closed form exists and ``adaptive`` for
    feedback techniques.  ``dca`` for a feedback technique promotes to
    ``adaptive`` (DCA semantics via epoch snapshots) with a warning — the old
    behaviour of silently downgrading to a synchronized/CCA path is gone.
    ``dca_sync`` is the paper's explicit AF-under-DCA fallback: the recursion
    runs under the lock (CCA calculation, DCA-style accounting).

    ``technique="auto"`` resolves to the ``select`` mode regardless of the
    requested mode: the SimAS selector (select/simas.py) picks — and keeps
    re-picking — the technique online, always under DCA claim semantics.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if technique == "auto":
        return "select", None
    tech = get_technique(technique)
    if mode == "auto":
        return ("dca" if tech.dca_supported else "adaptive"), None
    if mode == "adaptive":
        if not tech.requires_feedback:
            return "dca", (
                f"{technique} takes no feedback; 'adaptive' runs it as plain dca"
            )
        return "adaptive", None
    if mode == "dca" and not tech.dca_supported:
        return "adaptive", (
            f"{technique} has no closed form; honoring 'dca' through the "
            "adaptive epoch source (use mode='cca' or 'dca_sync' for the "
            "paper's synchronized fallback)"
        )
    if mode == "dca_sync" and not tech.requires_feedback:
        return "dca", (f"{technique} needs no synchronized calculation; using dca")
    return mode, None


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """Declarative scheduling config: one object names the whole policy.

    ``levels`` composes a hierarchy: ``((tech_a, P_a), (tech_b, P_b))`` means
    P_a groups claim global chunks under tech_a and each group's P_b workers
    self-schedule the local queue under tech_b (then ``technique``/``P`` are
    ignored for source construction).  ``params`` optionally carries a full
    DLSParams (σ, μ, h, ...); otherwise one is derived from N/P/min_chunk/seed.

    ``placement`` picks the claim substrate: ``"thread"`` (default) builds the
    in-process backends; ``"process"`` builds their cross-process analogues
    from repro.dist — shared-memory tables + shared counter for DCA, a
    foreman coordinator process for CCA/adaptive/select (DESIGN.md Sec. 10);
    ``"net"`` builds the networked analogues from repro.net — a remote
    fetch-and-add counter for DCA, a TCP network foreman for the rest
    (DESIGN.md Sec. 13).  Anything else raises ``PlacementError``.

    ``scenario`` (a ``PerturbationScenario``, select/scenarios.py) makes the
    built source scenario-driven: its calculation delay is injected with the
    simulators' placement semantics — inside the critical section for
    serialized (CCA-style) backends, concurrently on the claiming worker for
    DCA-style ones (``runtime.inject``).  Speed-profile stretching of the
    *workload* is the executors' job (they accept ``scenario=`` directly);
    a bare source only owns the claim side.
    """

    technique: str
    N: int
    P: int
    mode: str = "auto"
    min_chunk: int = 1
    seed: int = 0
    levels: Tuple[Tuple[str, int], ...] = ()
    params: Optional[DLSParams] = None
    placement: str = "thread"
    scenario: Optional[object] = None

    def __post_init__(self):
        validate_placement(self.placement)

    def to_params(self, N: Optional[int] = None, P: Optional[int] = None) -> DLSParams:
        if self.params is not None and N is None and P is None:
            return self.params
        base = self.params
        return DLSParams(
            N=N if N is not None else self.N,
            P=P if P is not None else self.P,
            min_chunk=base.min_chunk if base else self.min_chunk,
            seed=base.seed if base else self.seed,
            **(
                {
                    f.name: getattr(base, f.name)
                    for f in dataclasses.fields(DLSParams)
                    if f.name not in ("N", "P", "min_chunk", "seed")
                }
                if base
                else {}
            ),
        )

    @property
    def effective_mode(self) -> str:
        return resolve_mode(self.technique, self.mode)[0]


# ---------------------------------------------------------------------------
# StaticSource — precomputed DCA schedule, lock-free claims
# ---------------------------------------------------------------------------


class StaticSource(ChunkSource):
    """Chunks from a precomputed schedule; claim == one atomic fetch-and-add.

    The step counter is an ``itertools.count`` — ``next()`` on it is atomic
    in CPython, so the claim hot path takes no lock at all: the chunk lookup
    (pure table read) happens outside any critical section, which is exactly
    the paper's DCA execution model.
    """

    serialized = False

    def __init__(self, schedule: Schedule):
        self.schedule = schedule
        self._counter = itertools.count()
        self._next = self._counter.__next__
        # plain-int tables: list indexing beats numpy scalar extraction on
        # the per-claim hot path (BENCH_source_overhead)
        self._lo = schedule.offsets.tolist()
        self._hi = (schedule.offsets + schedule.sizes).tolist()
        self._num_steps = schedule.num_steps
        # completed-claim counter: next() on an itertools.count is an atomic
        # increment, and __reduce__ reads the current value without consuming
        # it — both single C calls under the GIL, so ``claimed`` is strictly
        # monotone with no check-then-store race anywhere
        self._done = itertools.count()
        self._done_next = self._done.__next__
        self._exhausted = False

    @classmethod
    def build(cls, technique: str, params: DLSParams) -> "StaticSource":
        return cls(build_schedule_dca(technique, params))

    def claim(self, worker: int = 0) -> Optional[Chunk]:
        step = self._next()  # the fetch-and-add
        if step >= self._num_steps:
            self._exhausted = True
            return None
        # count the completed claim (atomic increment — the old high-water
        # store let a claimer that slept between its fetch-and-add and the
        # store drag ``claimed``/``drained()`` backwards under concurrency;
        # a pure counter cannot regress)
        self._done_next()
        # closed form / table lookup — outside any lock
        return Chunk(step, self._lo[step], self._hi[step], worker)

    def claim_timed(self, worker: int = 0) -> Tuple[Optional[Chunk], Optional[float]]:
        return self.claim(worker), 0.0  # lock-free: it never waits

    def drained(self) -> bool:
        return self._exhausted or self.claimed >= self.schedule.num_steps

    @property
    def claimed(self) -> int:
        """Completed successful claims so far — strictly monotone (a pure
        counter), exact once drained, and never ahead of the chunks actually
        handed out."""
        if self._exhausted:
            return self.schedule.num_steps
        return self._done.__reduce__()[1][0]  # read without consuming

    def materialize(self) -> Schedule:
        return self.schedule


# ---------------------------------------------------------------------------
# CriticalSectionSource — the CCA baseline (recursion under the lock)
# ---------------------------------------------------------------------------


class AFEstimator:
    """Per-PE (μ, σ) running estimates for AF driven through ``report()``.

    The simulator's AFFeedback measures exact per-chunk iteration statistics;
    a live runtime only observes (chunk size, elapsed).  This estimator keeps
    a running mean of per-iteration times per PE and a Welford variance over
    the per-chunk means as the σ proxy."""

    def __init__(self, P: int, mu0: float, sigma0: float):
        self.mu_per_pe = np.full(P, mu0)
        self.sigma_per_pe = np.full(P, sigma0)
        self._count = np.zeros(P, dtype=np.int64)
        self._m2 = np.zeros(P)
        self.requesting_pe = 0

    @property
    def ready(self) -> bool:
        return bool((self._count > 0).all())

    def record(self, pe: int, size: int, t_compute: float, t_overhead: float = 0.0):
        mean = t_compute / max(size, 1)
        n = self._count[pe]
        w = 1.0 / (n + 1.0)
        delta = mean - self.mu_per_pe[pe]
        self.mu_per_pe[pe] += w * delta
        self._m2[pe] += delta * (mean - self.mu_per_pe[pe])
        if n > 0:
            self.sigma_per_pe[pe] = math.sqrt(max(self._m2[pe] / n, 0.0))
        self._count[pe] += 1


def _feedback_for(technique: str, params: DLSParams):
    """Default feedback object for a feedback technique (None otherwise)."""
    tech = get_technique(technique)
    if not tech.requires_feedback:
        return None
    if technique.startswith("awf_"):
        return AWFFeedback(params.P, awf_variant(technique))
    return AFEstimator(params.P, params.mu, params.sigma)


class CriticalSectionSource(ChunkSource):
    """CCA: chunk calculation inside the critical section (paper baseline).

    The recursion may consult ``feedback`` (AF/AWF); ``report`` feeds it.
    ``calc_delay_s`` injects the paper's calculation slowdown *inside* the
    lock — the serialization the experiments measure.
    """

    serialized = True

    def __init__(
        self,
        technique: str,
        params: DLSParams,
        feedback=None,
        calc_delay_s: float = 0.0,
    ):
        self.technique = technique
        self.tech = get_technique(technique)
        self.params = params
        self.feedback = feedback if feedback is not None else _feedback_for(technique, params)
        self.calc_delay_s = calc_delay_s
        self._lock = threading.Lock()
        self._step = 0
        self._lp = 0
        self._remaining = params.N
        self._prev_raw = 0.0

    def claim(self, worker: int = 0) -> Optional[Chunk]:
        return self._claim(worker, self._lock)

    def claim_timed(self, worker: int = 0) -> Tuple[Optional[Chunk], Optional[float]]:
        lock = tracing.TimedLock(self._lock)
        return self._claim(worker, lock), lock.wait_s

    def _claim(self, worker: int, lock) -> Optional[Chunk]:
        worker = worker % self.params.P  # PE slot (feedback arrays are [P])
        with lock:
            if self._remaining <= 0:
                return None
            if self.calc_delay_s:
                # reprolint: waive[RPL001] CCA's measured cost IS this serialized calc delay
                time.sleep(self.calc_delay_s)  # serialized, like the CCA master
            fb = self.feedback
            if fb is not None:
                fb.requesting_pe = worker
                if (
                    self._step
                    and self._step % self.params.P == 0
                    and hasattr(fb, "end_batch")
                ):
                    fb.end_batch()  # AWF batch boundary (B/D flush, C/E refresh)
            raw = self.tech.recursive_step(
                self._step, self._remaining, self._prev_raw, self.params, fb
            )
            k = int(min(max(int(raw), self.params.min_chunk), self._remaining))
            step, lo = self._step, self._lp
            self._prev_raw = raw if raw > 0 else k
            self._step += 1
            self._lp += k
            self._remaining -= k
            return Chunk(step, lo, lo + k, worker)

    def report(self, chunk: Chunk, elapsed: float, overhead: float = 0.0) -> None:
        fb = self.feedback
        if fb is not None and hasattr(fb, "record"):
            with self._lock:
                fb.record(chunk.worker, chunk.size, elapsed, overhead)

    def drained(self) -> bool:
        return self._remaining <= 0

    def fast_forward(self, step: int, lp: int, prev_raw: float = 0.0) -> None:
        """Re-seed a fresh source to resume after ``step`` chunks covering
        ``[0, lp)`` were already served — the foreman supervisor's recovery
        hook (dist/sources.py): a restarted coordinator rebuilds its inner
        source and fast-forwards it from the shared progress block so no
        range is served twice.  ``prev_raw`` restores the recursion's
        previous-chunk state for techniques that consume it."""
        with self._lock:
            self._step = int(step)
            self._lp = int(lp)
            self._remaining = self.params.N - int(lp)
            self._prev_raw = float(prev_raw)

    @property
    def claimed(self) -> int:
        """Successful claims so far (== chunks the master has served)."""
        return self._step

    def materialize(self) -> Schedule:
        """Drain a *fresh* copy of this source into a full Schedule (only
        meaningful without feedback, where the sequence is claim-order
        independent — equals ``build_schedule_cca``)."""
        if self.tech.requires_feedback:
            raise FeedbackScheduleError(
                f"{self.technique} chunks depend on execution feedback; "
                "its schedule cannot be materialized ahead of time"
            )
        return build_schedule_cca(self.technique, self.params)


# ---------------------------------------------------------------------------
# AdaptiveSource — AWF-B/C/D/E and AF under DCA semantics
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _EpochSnapshot:
    """Immutable per-epoch feedback state published to workers.

    Together with the queue-head read R, this is everything a chunk-size
    calculation consumes — a pure function of (snapshot, worker, R) — so the
    calculation happens outside the lock; only the fetch-and-add of the
    resulting size serializes (DCA semantics)."""

    epoch: int
    weights: Optional[np.ndarray] = None  # AWF: adapted weights (sum == P)
    mu: Optional[np.ndarray] = None  # AF: per-PE mean iteration time
    sigma: Optional[np.ndarray] = None  # AF: per-PE std estimate
    warm: bool = False  # AF: every PE has reported


class AdaptiveSource(ChunkSource):
    """Adaptive techniques with the calculation outside the critical section.

    Epoch scheme: an epoch admits up to P claims against one published
    snapshot.  A claim (a) reads the snapshot (atomic reference read),
    (b) computes its chunk size from it lock-free, (c) fetch-and-adds that
    size on the queue head under the lock (two integer ops), retrying from
    the fresh snapshot in the rare case the epoch rolled in between.  The
    P-th claim republishes the snapshot from the accumulated ``report()``
    timings — O(P) work once per P chunks, amortized O(1) per claim.

    The remaining-work input R is an *unlocked read of the queue head*
    (``N - lp``): like the paper's shared step counter it is an input to the
    calculation, not a critical section — a stale read only makes a chunk
    a hair larger, and coverage never depends on it.  This reproduces the
    live-R decay of the CCA recursion without serializing anything.

    Coverage is structural: the queue head only advances by claimed sizes and
    the last claim clamps to N, so chunks tile [0, N) exactly no matter what
    the weights do.  With weights summing to P, claims follow the factoring
    share w·R/(2P), giving ~P·log2(N/P) chunks like FAC.
    """

    serialized = False

    def __init__(self, technique: str, params: DLSParams, feedback=None):
        tech = get_technique(technique)
        if not tech.requires_feedback:
            raise ValueError(
                f"{technique} is not adaptive; use StaticSource "
                "(closed forms) instead"
            )
        self.technique = technique
        self.params = params
        self.is_awf = technique.startswith("awf_")
        self.feedback = feedback if feedback is not None else _feedback_for(technique, params)
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._lp = 0
        self._step = 0
        self._epoch_claims = 0
        self.epochs_published = 0
        self._snapshot = self._build_snapshot(0)

    # -- snapshot machinery ----------------------------------------------------

    def _build_snapshot(self, epoch: int) -> _EpochSnapshot:
        fb = self.feedback
        if self.is_awf:
            snap = getattr(fb, "snapshot_weights", None)
            weights = snap() if snap is not None else fb.weights.copy()
            return _EpochSnapshot(epoch=epoch, weights=weights)
        return _EpochSnapshot(
            epoch=epoch,
            mu=np.array(fb.mu_per_pe, dtype=np.float64),
            sigma=np.array(fb.sigma_per_pe, dtype=np.float64),
            warm=fb.ready,
        )

    def _publish_locked(self):
        with self._stats_lock:
            if hasattr(self.feedback, "end_batch"):
                self.feedback.end_batch()
            self.epochs_published += 1
            self._epoch_claims = 0
            self._snapshot = self._build_snapshot(self.epochs_published)

    def _size_for(self, worker: int, snap: _EpochSnapshot, R: float) -> int:
        """Chunk size — pure function of (snapshot, worker, counter read R);
        no state is mutated here."""
        p = self.params
        if R <= 0:
            return 0
        if self.is_awf:
            w = float(snap.weights[worker])
            k = math.ceil(w * R / (2.0 * p.P))
        elif not snap.warm:
            k = p.min_chunk  # AF warm-up: learn (μ, σ) from single iterations
        else:
            mus = np.maximum(snap.mu, 1e-12)
            d = float(np.sum(snap.sigma ** 2 / mus))
            e = 1.0 / float(np.sum(1.0 / mus))
            mu_p = max(float(mus[worker]), 1e-12)
            k = (d + 2.0 * e * R - math.sqrt(d * d + 4.0 * d * e * R)) / (2.0 * mu_p)
        return max(int(k), max(p.min_chunk, 1))

    # -- protocol ----------------------------------------------------------------

    def claim(self, worker: int = 0) -> Optional[Chunk]:
        worker = worker % self.params.P  # PE slot (feedback arrays are [P])
        N = self.params.N
        while True:
            snap = self._snapshot  # atomic reference read
            R = N - self._lp  # advisory queue-head read (atomic int read)
            k = self._size_for(worker, snap, R)  # calc OUTSIDE the lock
            with self._lock:  # the fetch-and-add
                if self._lp >= N:
                    return None
                if self._snapshot is not snap:
                    continue  # epoch rolled under us: recompute (rare)
                step, lo = self._step, self._lp
                k = min(k, N - lo)
                self._step += 1
                self._lp += k
                self._epoch_claims += 1
                if self._epoch_claims >= self.params.P or self._lp >= N:
                    self._publish_locked()
                return Chunk(step, lo, lo + k, worker, epoch=snap.epoch)

    def report(self, chunk: Chunk, elapsed: float, overhead: float = 0.0) -> None:
        with self._stats_lock:
            self.feedback.record(chunk.worker, chunk.size, elapsed, overhead)

    def drained(self) -> bool:
        return self._lp >= self.params.N

    def fast_forward(self, step: int, lp: int, prev_raw: float = 0.0) -> None:
        """Resume-after-restart re-seed (see CriticalSectionSource): the
        queue head jumps to ``lp`` so [0, lp) is never re-served.  Feedback
        state restarts cold — the epoch scheme re-learns it from subsequent
        reports, which only perturbs chunk *sizes*, never coverage."""
        with self._lock:
            self._step = int(step)
            self._lp = int(lp)

    @property
    def claimed(self) -> int:
        """Successful claims so far."""
        return self._step


# ---------------------------------------------------------------------------
# HierarchicalSource — two-level composition
# ---------------------------------------------------------------------------


class HierarchicalSource(ChunkSource):
    """Groups claim global chunks; group workers drain local sub-sources.

    ``global_source`` hands out group-level chunks; ``local_factory(n)``
    builds the source a group uses to subdivide an n-iteration global chunk.
    ``group_of`` maps a worker id to its group.  Global contention is one
    claim per *group* chunk — the scaling story of the hierarchical scheme.

    ``report`` feedback is routed to the *local* source that issued the
    chunk, in the chunk's local coordinates — an adaptive local queue under
    a static global schedule adapts as intended.  The global level receives
    no per-chunk feedback (its chunks are whole group queues, whose timing
    is not chunk-resolved).
    """

    serialized = False
    # timing models price claims through this source as amortized coarse-batch
    # fetches (NetworkModel.tree_claim_s), not per-claim round-trips: the
    # global level fetches one batch per group queue, locals re-serve it
    amortizes_network = True

    def __init__(
        self,
        global_source: ChunkSource,
        local_factory: Callable[[int], ChunkSource],
        n_groups: int,
        group_of: Optional[Callable[[int], int]] = None,
    ):
        self.global_source = global_source
        self.local_factory = local_factory
        self.n_groups = n_groups
        self.group_of = group_of or (lambda w: w % n_groups)
        self._glock = [threading.Lock() for _ in range(n_groups)]
        self._group: List[Optional[Tuple[int, ChunkSource]]] = [None] * n_groups
        self._steps = itertools.count()
        # global step -> (issuing local source, local chunk); popped by report
        self._issued: Dict[int, Tuple[ChunkSource, Chunk]] = {}

    def claim(self, worker: int = 0) -> Optional[Chunk]:
        g = self.group_of(worker)
        with self._glock[g]:
            while True:
                state = self._group[g]
                if state is not None:
                    base, local = state
                    c = local.claim(worker)
                    if c is not None:
                        out = Chunk(
                            next(self._steps), base + c.lo, base + c.hi, worker
                        )
                        if (
                            getattr(local, "feedback", None) is not None
                            or getattr(local, "estimator", None) is not None
                        ):
                            # track only feedback-consuming locals (adaptive
                            # feedback or a SelectingSource estimator): static
                            # locals ignore reports, and an unreported chunk
                            # would otherwise pin a dict entry forever
                            self._issued[out.step] = (local, c)
                        return out
                    self._group[g] = None  # local queue drained
                gchunk = self.global_source.claim(worker)
                if gchunk is None:
                    return None
                self._group[g] = (gchunk.lo, self.local_factory(gchunk.size))

    def report(self, chunk: Chunk, elapsed: float, overhead: float = 0.0) -> None:
        issued = self._issued.pop(chunk.step, None)
        if issued is not None:
            local, local_chunk = issued
            local.report(local_chunk, elapsed, overhead)

    def drained(self) -> bool:
        return self.global_source.drained() and all(
            s is None for s in self._group
        )

    @property
    def global_claims(self) -> int:
        """Fetch-and-adds on the *global* counter (vs one per chunk, flat)."""
        return getattr(self.global_source, "claimed", 0)


# ---------------------------------------------------------------------------
# Factories
# ---------------------------------------------------------------------------


_DEPRECATED_FACTORY_MSG = (
    "{name}() is deprecated; build sources through the one entry point "
    "make_source(ScheduleSpec(..., placement={placement!r})) — it dispatches "
    "to the same backends (see the README migration table)"
)


def _source_for(
    technique: str,
    params: DLSParams,
    mode: str = "auto",
    feedback=None,
    calc_delay_s: float = 0.0,
    warn: bool = True,
) -> ChunkSource:
    """Thread-placement internals behind ``make_source``: build the backend
    for (technique, mode); warns when the effective mode differs from the
    requested one (the old silent fallback).

    Module-level (not a closure) on purpose: the process/net foremen pickle
    ``functools.partial(_source_for, ...)`` as their inner factory.

    ``technique="auto"`` builds a ``SelectingSource`` (select/simas.py): the
    SimAS selector picks the technique online from claim/report feedback.
    """
    if technique == "auto":
        from repro.select.simas import SelectingSource  # deferred: select imports core

        return SelectingSource(params)
    effective, message = resolve_mode(technique, mode)
    if message and warn:
        warnings.warn(message, ModeDowngradeWarning, stacklevel=2)
    if effective == "dca":
        return StaticSource.build(technique, params)
    if effective == "adaptive":
        return AdaptiveSource(technique, params, feedback=feedback)
    # cca and dca_sync: the recursion runs under the lock.  dca_sync differs
    # only in accounting (no master displacement) — a timing-model concern,
    # not a source concern.
    return CriticalSectionSource(
        technique, params, feedback=feedback, calc_delay_s=calc_delay_s
    )


def source_for(technique, params, mode="auto", feedback=None,
               calc_delay_s=0.0, warn=True) -> ChunkSource:
    """Deprecated alias for the thread-placement internals; use
    ``make_source(ScheduleSpec(...))`` — bit-identical, but warns."""
    warnings.warn(
        _DEPRECATED_FACTORY_MSG.format(name="source_for", placement="thread"),
        DeprecationWarning,
        stacklevel=2,
    )
    return _source_for(technique, params, mode, feedback=feedback,
                       calc_delay_s=calc_delay_s, warn=warn)


def make_source(spec: ScheduleSpec, **kw) -> ChunkSource:
    """THE source-construction entry point: build a ChunkSource from a
    declarative spec (hierarchical if ``spec.levels`` names more than one
    level; cross-process/networked via ``spec.placement``; scenario-driven
    claim delays — and constant network claim costs — if ``spec.scenario``
    is set).  The legacy factories (``source_for``, ``process_source_for``,
    ``net_source_for``) are deprecated aliases over the same placement-
    dispatched internals."""
    if spec.scenario is not None:
        if kw.get("calc_delay_s"):
            raise ValueError("pass the delay through spec.scenario, not calc_delay_s")
        delay = float(spec.scenario.delay_calc_s)
        network = getattr(spec.scenario, "network", None)
        if spec.levels:
            # one delay per *worker* claim, like the simulators: inject at
            # the composed outer source — NOT inside the global level's
            # critical section too, which would charge a second delay on
            # every group-queue refill
            src = _make_source_base(spec, **kw)
        else:
            # serialized backends take the delay inside their critical
            # section at construction — plus the reply's port serialization,
            # which drains the master's single port before the next claim is
            # served (the request leg drains the *claimer's* port, so it and
            # the wire legs are per-claimer-concurrent: the executors pay
            # them, via ScenarioInjector.claim_delay) — while DCA-style
            # backends get wrapped below
            if network is not None and spec.effective_mode in ("cca", "dca_sync"):
                delay = delay + network.serialization_s
            kw["calc_delay_s"] = delay
            src = _make_source_base(spec, **kw)
        inject = delay
        if not src.serialized and network is not None:
            if getattr(src, "amortizes_network", False):
                inject = inject + network.tree_claim_s
            else:
                inject = inject + network.dca_claim_s()
        if not src.serialized and inject:
            from repro.runtime.inject import InjectedSource  # runtime imports core

            src = InjectedSource(src, inject)
        return src
    return _make_source_base(spec, **kw)


def _make_source_base(spec: ScheduleSpec, **kw) -> ChunkSource:
    validate_placement(spec.placement)  # defensive: __post_init__ bypassed
    if spec.placement == "process":
        from repro.dist.sources import _process_source_for  # deferred: dist imports core

        if spec.levels:
            raise NotImplementedError(
                "hierarchical + placement='process' is not supported yet; "
                "compose a ForemanSource-backed global level explicitly"
            )
        return _process_source_for(spec.technique, spec.to_params(), spec.mode, **kw)
    if spec.placement == "net":
        from repro.net.sources import _net_source_for  # deferred: net imports core

        if spec.levels:
            raise NotImplementedError(
                "hierarchical + placement='net' is not supported yet; use "
                "repro.net.SimulatedCluster(transport='tree') for the "
                "node-master tree"
            )
        return _net_source_for(spec.technique, spec.to_params(), spec.mode, **kw)
    if spec.levels:
        if len(spec.levels) < 2:
            raise ValueError("hierarchy needs >= 2 levels: ((tech, P), ...)")
        if len(spec.levels) > 2:
            raise NotImplementedError("only two-level hierarchies are supported")
        (g_tech, n_groups), (l_tech, w_per_group) = spec.levels
        global_source = _source_for(
            g_tech, spec.to_params(P=n_groups), spec.mode, **kw
        )
        local_mode = resolve_mode(l_tech, spec.mode)[0]

        def local_factory(n: int) -> ChunkSource:
            return _source_for(
                l_tech, spec.to_params(N=n, P=w_per_group), local_mode, warn=False
            )

        return HierarchicalSource(
            global_source,
            local_factory,
            n_groups,
            group_of=lambda w: (w // w_per_group) % n_groups,
        )
    return _source_for(spec.technique, spec.to_params(), spec.mode, **kw)


def materialize(spec_or_source) -> Schedule:
    """Full Schedule for a spec/source whose chunk sequence is execution-
    independent (Static and non-feedback CriticalSection sources)."""
    src = (
        make_source(spec_or_source)
        if isinstance(spec_or_source, ScheduleSpec)
        else spec_or_source
    )
    mat = getattr(src, "materialize", None)
    if mat is None:
        raise ValueError(
            f"{type(src).__name__} chunks depend on execution; no static schedule"
        )
    return mat()
