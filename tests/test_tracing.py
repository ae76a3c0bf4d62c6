"""The program's tracing switch (core/tracing.py): off it costs the worker
loop nothing; on, the executor's records carry the claim request, lock wait
and CPU time, and the spans land in a profiler trace beside the device's.

The benchmark's trace reduction and metric readers (benchmarks/chip/) read
what these spans and records hold, so they are tested here on both."""

import gc
import importlib
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.core import tracing
from repro.core.executor import ChunkRecord, SelfSchedulingExecutor
from repro.core.source import (
    AdaptiveSource,
    CriticalSectionSource,
    ScheduleSpec,
    StaticSource,
    make_source,
)
from repro.core.techniques import DLSParams
from repro.select.scenarios import PerturbationScenario

CHIP_BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP_BENCH))

import reduction  # noqa: E402

N, P, W = 4096, 16, 4


def _run(ex, work_s=0.0):
    def fn(lo, hi):
        if work_s:
            time.sleep(work_s)

    ex.run(fn, n_workers=W)
    return ex.records


@pytest.fixture
def annotations(monkeypatch):
    """Counts the ``TraceAnnotation``s built while the test runs."""
    import jax.profiler

    made = []
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return made


# -- off ----------------------------------------------------------------------


@pytest.mark.parametrize("mode,delay", [("dca", 0.0), ("cca", 0.0005)])
def test_off_builds_no_span_and_records_no_wait_or_cpu(annotations, mode, delay):
    ex = SelfSchedulingExecutor("fac", DLSParams(N=N, P=P), mode, calc_delay_s=delay)
    recs = _run(ex)
    assert annotations == []
    assert sum(r.hi - r.lo for r in recs) == N
    assert all(r.wait_s is None and r.cpu_s is None for r in recs)
    assert all(r.t_req <= r.t_claim <= r.t_done for r in recs)


def test_off_after_on_leaves_gc_callbacks_and_switch_as_they_were():
    before = list(gc.callbacks)
    assert not tracing.enabled()
    with tracing.on():
        assert tracing.enabled()
        assert len(gc.callbacks) == len(before) + 1
        with tracing.on():  # nested: still on, one callback
            assert len(gc.callbacks) == len(before) + 1
        assert tracing.enabled()
    assert not tracing.enabled()
    assert gc.callbacks == before


def test_chunk_record_keeps_its_positional_form():
    r = ChunkRecord(3, 10, 20, 1, 1.5, 2.5)
    assert (r.step, r.lo, r.hi, r.worker, r.t_claim, r.t_done) == (3, 10, 20, 1, 1.5, 2.5)
    assert r.t_req is None and r.wait_s is None and r.cpu_s is None


# -- on -----------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["dca", "cca"])
def test_on_records_request_claim_done_and_cpu(annotations, mode):
    ex = SelfSchedulingExecutor("fac", DLSParams(N=N, P=P), mode)
    with tracing.on():
        recs = _run(ex, work_s=0.0002)
    assert sum(r.hi - r.lo for r in recs) == N
    for r in recs:
        assert r.t_req <= r.t_claim <= r.t_done
        assert r.cpu_s >= 0.0 and r.wait_s >= 0.0
    # one claim and one report span per chunk, and the claim that found
    # the source drained
    assert annotations.count("report") == len(recs)
    assert annotations.count("claim") == len(recs) + W


@pytest.mark.parametrize("mode", ["dca", "cca"])
def test_traced_loop_under_many_threads_keeps_every_chunk_once(mode):
    ex = SelfSchedulingExecutor("ss", DLSParams(N=N, P=P), mode)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with tracing.on():
            t = threading.Thread(target=ex.run, args=(lambda lo, hi: gc.collect(0), 32))
            t.start()
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not t.is_alive()
    assert sorted(r.lo for r in ex.records) == list(range(N))
    assert all(r.wait_s >= 0.0 and r.cpu_s >= 0.0 for r in ex.records)


def test_critical_section_delay_shows_as_lock_wait():
    ex = SelfSchedulingExecutor("fac", DLSParams(N=N, P=P), "cca", calc_delay_s=0.002)
    with tracing.on():
        recs = _run(ex)
    assert sum(r.wait_s for r in recs) / len(recs) > 0.0
    # the serialized delay is inside the claim: each claim holds it
    assert min(r.t_claim - r.t_req for r in recs) >= 0.002


def test_static_source_never_waits():
    ex = SelfSchedulingExecutor("fac", DLSParams(N=N, P=P), "dca",
                                source=StaticSource.build("fac", DLSParams(N=N, P=P)))
    with tracing.on():
        recs = _run(ex, work_s=0.0001)
    assert {r.wait_s for r in recs} == {0.0}


def test_injected_source_forwards_the_wait_and_pays_the_delay():
    spec = ScheduleSpec("fac", N=N, P=P, mode="dca",
                        scenario=PerturbationScenario.constant(P, delay_calc_s=0.001))
    src = make_source(spec)
    assert getattr(src, "injects_delay", False)
    chunk, wait_s = src.claim_timed(0)
    assert chunk is not None and wait_s == 0.0
    ex = SelfSchedulingExecutor("fac", spec.to_params(), "dca", source=make_source(spec))
    with tracing.on():
        recs = _run(ex)
    assert min(r.t_claim - r.t_req for r in recs) >= 0.001
    assert {r.wait_s for r in recs} == {0.0}


def test_a_source_that_cannot_tell_its_wait_says_none():
    src = AdaptiveSource("awf_b", DLSParams(N=N, P=P))
    chunk, wait_s = src.claim_timed(0)
    assert chunk is not None and wait_s is None


def test_claim_timed_hands_out_the_claim_sequence():
    params = DLSParams(N=N, P=P)
    plain, timed = CriticalSectionSource("fac", params), CriticalSectionSource("fac", params)
    while (c := plain.claim(0)) is not None:
        d, wait_s = timed.claim_timed(0)
        assert (d.step, d.lo, d.hi) == (c.step, c.lo, c.hi) and wait_s == 0.0
    assert timed.claim_timed(0) == (None, 0.0)


def test_timed_lock_measures_the_wait_for_a_held_lock():
    lock = threading.Lock()
    free = tracing.TimedLock(lock)
    with free:
        assert lock.locked()
    assert free.wait_s == 0.0 and not lock.locked()
    lock.acquire()
    threading.Timer(0.02, lock.release).start()
    held = tracing.TimedLock(lock)
    with held:
        pass
    assert held.wait_s >= 0.015


def test_gc_stats_count_collections_while_on():
    before = tracing.gc_stats()
    gc.collect()
    assert tracing.gc_stats() == before  # off: not counted
    with tracing.on():
        gc.collect()
    after = tracing.gc_stats()
    assert after["collections"][2] == before["collections"][2] + 1
    assert after["seconds"] > before["seconds"]


# -- the profiler trace -------------------------------------------------------


def test_spans_land_on_worker_threads_of_the_profiler_trace():
    import jax

    ex = SelfSchedulingExecutor("fac", DLSParams(N=N, P=P), "cca", calc_delay_s=0.001)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="trace-test-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(reduction.WINDOW), tracing.on():
                _run(ex, work_s=0.0002)
                gc.collect()
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(tmp).rglob("*.xplane.pb")
        spans = reduction.load(str(path), tracing.SPANS)["spans"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    by_name = {}
    for _, _, name, thread in spans:
        by_name.setdefault(name, set()).add(thread)
    (main,) = by_name[reduction.WINDOW]
    for name in ("claim", "lock_wait", "report"):
        assert by_name.get(name), name
        assert main not in by_name[name], name
    assert main in by_name.get("gc", ())


# -- the benchmark's readers ----------------------------------------------------


def _frames(*records):
    return SimpleNamespace(frames=[SimpleNamespace(records=list(records))], trace=None,
                           chips=1)


def _rec(t_req, t_claim, t_done, wait_s=None, cpu_s=None):
    return ChunkRecord(0, 0, 1, 0, t_claim, t_done, t_req, wait_s, cpu_s)


def _read(name, run):
    return importlib.import_module(f"metrics.{name}").read(run)


def test_claim_us_reads_request_to_claim():
    run = _frames(_rec(1.0, 1.000010, 2.0), _rec(3.0, 3.000030, 4.0))
    assert _read("claim_us", run) == pytest.approx(20.0)
    # a program whose records hold no request time reads nothing
    assert _read("claim_us", _frames(ChunkRecord(0, 0, 1, 0, 1.0, 2.0))) is None
    assert _read("claim_us", SimpleNamespace(frames=[object()])) is None


def test_lock_wait_us_reads_only_the_claims_that_report_a_wait():
    run = _frames(_rec(0, 1, 2, wait_s=0.0001), _rec(0, 1, 2, wait_s=0.0003), _rec(0, 1, 2))
    assert _read("lock_wait_us", run) == pytest.approx(200.0)
    assert _read("lock_wait_us", _frames(_rec(0, 1, 2))) is None


def test_run_cpu_us_reads_the_thread_cpu_per_chunk():
    run = _frames(_rec(0, 1, 2, cpu_s=0.001), _rec(0, 1, 2, cpu_s=0.003))
    assert _read("run_cpu_us", run) == pytest.approx(2000.0)
    assert _read("run_cpu_us", _frames(_rec(0, 1, 2))) is None
