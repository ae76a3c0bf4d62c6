"""Program spans and counters, off unless switched on.

A span is a ``jax.profiler.TraceAnnotation``: it lands on the profiler's
host clock, in the same trace as the device's operations, so a reader of the
trace can tell what each worker was doing while the device sat idle.  With no
profiler running a span still costs about a microsecond to build, so the hot
paths read the switch once per run (``enabled()``) and pick a loop that opens
none.

Spans the program opens (``SPANS``):

* ``claim``: a worker asking its source for a chunk, with the injected
  calculation delay and network legs (``core/executor.py``);
* ``lock_wait``: a claimer waiting for a source's lock (``TimedLock``);
* ``report``: feedback to the source and the record append;
* ``gc``: a garbage collection, on the thread that triggered it;
* ``dispatch``: a device body's call, from the call to its return with the
  result not yet waited for (``core/device_body.py``);
* ``block``: the wait for that result (``block_until_ready``).

While tracing is on, ``gc_stats()`` also counts collections per generation
and the seconds they took.

Device scopes the program opens (``SCOPES``): ``jax.named_scope``s in the
SPMD round forms (``core/sspmd.py``).  A scope only names the ops traced
inside it, in their metadata (``op_name``), so it needs no switch and adds no
op; a device trace finds it in each op's name path.

* ``dca_schedule``: the closed-form chunk calculation of every round at once
  (``dca_schedule_stateless``);
* ``dca_round``: one DCA round (``dca_round_assignments`` and its stateless
  form);
* ``cca_round``: one CCA baseline round (``cca_round_assignments``);
* ``cca_bcast``: inside it, the master's chunks broadcast by three ``psum``s.
"""

from __future__ import annotations

import contextlib
import gc
import time

__all__ = ["SPANS", "SCOPES", "enabled", "on", "span", "gc_stats", "TimedLock"]

SPANS = ("claim", "lock_wait", "report", "gc", "dispatch", "block")
SCOPES = ("dca_schedule", "dca_round", "cca_round", "cca_bcast")

_enabled = False
_gc = {"collections": [0, 0, 0], "seconds": 0.0}
_gc_open = None  # (span, start) of the collection in progress


def enabled() -> bool:
    """Whether the program opens its spans and reads its counters."""
    return _enabled


@contextlib.contextmanager
def on():
    """Switch tracing on for the block (process-wide), then back to what it was."""
    global _enabled
    was = _enabled
    if not was:
        gc.callbacks.append(_on_gc)
    _enabled = True
    try:
        yield
    finally:
        _enabled = was
        if not was:
            gc.callbacks.remove(_on_gc)


def span(name: str):
    """A span named ``name`` on the profiler's host clock (a context manager)."""
    from jax import profiler

    return profiler.TraceAnnotation(name)


def gc_stats() -> dict:
    """Collections per generation and seconds in them, summed over every
    stretch that tracing was on."""
    return {"collections": list(_gc["collections"]), "seconds": _gc["seconds"]}


def _on_gc(phase, info):
    global _gc_open
    if phase == "start":
        s = span("gc")
        s.__enter__()
        _gc_open = (s, time.perf_counter())
    elif _gc_open is not None:
        s, t0 = _gc_open
        _gc_open = None
        _gc["seconds"] += time.perf_counter() - t0
        _gc["collections"][info["generation"]] += 1
        s.__exit__(None, None, None)


class TimedLock:
    """``with TimedLock(lock):`` holds ``lock``; ``wait_s`` is how long the
    caller waited for it, under a ``lock_wait`` span, and 0.0 where the lock
    was free.  One instance per acquisition."""

    __slots__ = ("_lock", "wait_s")

    def __init__(self, lock):
        self._lock = lock
        self.wait_s = 0.0

    def __enter__(self):
        if not self._lock.acquire(blocking=False):
            t0 = time.perf_counter()
            with span("lock_wait"):
                self._lock.acquire()
            self.wait_s = time.perf_counter() - t0
        return self

    def __exit__(self, *exc):
        self._lock.release()
