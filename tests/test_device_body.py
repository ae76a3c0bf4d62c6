"""The program's device-call layer (core/device_body.py) on the CPU: one call
per tile of a chunk, each waited for; results as calling the tile tile by
tile, under the executor as alone; untraced, no span and no clock; traced,
the ``dispatch`` and ``block`` spans, one wall-clock pair a call, and both
spans on the workers' threads of a profiler trace."""

import contextlib
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import device_body, tracing
from repro.core.device_body import DeviceBody

CHIP_BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP_BENCH))

import reduction  # noqa: E402

TILE = 1024


@pytest.fixture(scope="module")
def tile():
    """Each iteration's index plus the offset argument; 0 past the tile's size."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def body(chunk, offset):
        idx = chunk[0] + jnp.arange(TILE, dtype=jnp.int32)
        return jnp.where(jnp.arange(TILE) < chunk[1], idx + offset, 0)
    return body


@pytest.fixture(scope="module")
def offset():
    import jax
    import jax.numpy as jnp

    return jax.device_put(jnp.int32(7))


@pytest.fixture
def spans(monkeypatch):
    """The names of the program spans opened, in order."""
    opened, real = [], tracing.span

    def span(name):
        opened.append(name)
        return real(name)
    monkeypatch.setattr(tracing, "span", span)
    return opened


@pytest.fixture
def clock_reads(monkeypatch):
    """The names of the clocks the layer reads, in order."""
    read = []

    def counted(name):
        real = getattr(time, name)

        def clock():
            read.append(name)
            return real()
        return clock
    monkeypatch.setattr(device_body, "time", SimpleNamespace(
        perf_counter=counted("perf_counter"), thread_time=counted("thread_time")))
    return read


@pytest.mark.parametrize("lo,hi", [(0, 1024), (0, 3000), (2048, 2100), (5, 6), (100, 4196)],
                         ids=["one_tile", "ragged_last", "shorter_than_a_tile", "one_iteration",
                              "unaligned"])
def test_results_equal_the_body_called_tile_by_tile(tile, offset, lo, hi):
    body = DeviceBody(tile, TILE, offset)
    body(lo, hi)
    want = [(a, min(TILE, hi - a)) for a in range(lo, hi, TILE)]
    assert [(a, size) for a, size, _ in body.results] == want
    for (a, size, out), (b, bsize) in zip(body.results, want):
        direct = tile(np.array([b, bsize], np.int32), offset)
        assert np.array_equal(np.asarray(out), np.asarray(direct))
        assert np.array_equal(np.asarray(out)[:size], np.arange(a, a + size) + 7)


@pytest.mark.parametrize("technique,mode", [("fac", "dca"), ("fac", "cca"), ("static", "dca")])
def test_executor_covers_every_iteration_once(tile, offset, technique, mode):
    from repro.core.executor import SelfSchedulingExecutor
    from repro.core.source import ScheduleSpec, make_source

    n = 4096
    spec = ScheduleSpec(technique, N=n, P=16, mode=mode)
    body = DeviceBody(tile, TILE, offset)
    ex = SelfSchedulingExecutor(spec.technique, spec.to_params(), spec.mode,
                                source=make_source(spec))
    ex.run(body, n_workers=4)
    got = np.zeros(n, np.int64)
    for a, size, out in body.results:
        got[a:a + size] += 1
        assert np.array_equal(np.asarray(out)[:size], np.arange(a, a + size) + 7)
    assert np.all(got == 1)
    assert len(body.results) == len(ex.records)  # no chunk exceeds a tile here


def test_untraced_body_opens_no_span_and_reads_no_clock(tile, offset, spans, clock_reads):
    body = DeviceBody(tile, TILE, offset)
    with tracing.on():  # switched on after the body was built: it stays untraced
        body(0, 3000)
    assert spans == [] and clock_reads == []
    assert body.stamps == [] and len(body.results) == 3


def test_traced_body_opens_both_spans_and_stamps_each_call(tile, offset, spans, clock_reads):
    with tracing.on():
        body = DeviceBody(tile, TILE, offset)
        body(0, 3000)
        body(4000, 4096)
    assert spans == ["dispatch", "block"] * 4
    assert clock_reads == ["perf_counter"] * 3 * 4  # three points a call
    assert len(body.stamps) == len(body.results) == 4
    assert all(d >= 0 and b >= 0 for d, b in body.stamps)


def test_dispatch_stamp_covers_the_callers_own_work():
    """A tile that burns 2 ms of its thread's CPU before it returns: the
    burn lands in the call's wall stamp, which is no less than that CPU time."""
    import jax.numpy as jnp

    done = jnp.zeros(1)

    def burn(chunk):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.002:
            pass
        return done

    with tracing.on():
        body = DeviceBody(burn, TILE)
        body(0, 2 * TILE)
    assert len(body.stamps) == 2
    assert all(dispatch_s >= 0.002 for dispatch_s, _ in body.stamps)


@pytest.mark.parametrize("traced", [False, True])
def test_an_error_in_the_body_reaches_the_caller(traced):
    def tile(chunk, offset):
        raise ValueError(f"no tile at {int(chunk[0])}")

    with tracing.on() if traced else contextlib.nullcontext():
        body = DeviceBody(tile, TILE, 0)
    with pytest.raises(ValueError, match="no tile at 0"):
        body(0, 10)
    assert body.results == [] and body.stamps == []


def test_spans_land_on_worker_threads_of_the_profiler_trace(tile, offset):
    import jax

    from repro.core.executor import SelfSchedulingExecutor
    from repro.core.source import ScheduleSpec, make_source

    spec = ScheduleSpec("fac", N=4096, P=16, mode="dca")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="trace-test-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(reduction.WINDOW), tracing.on():
                body = DeviceBody(tile, TILE, offset)
                SelfSchedulingExecutor(spec.technique, spec.to_params(), spec.mode,
                                       source=make_source(spec)).run(body, n_workers=4)
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(tmp).rglob("*.xplane.pb")
        spans = reduction.load(str(path), tracing.SPANS)["spans"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    by_name = {}
    for _, _, name, thread in spans:
        by_name.setdefault(name, []).append(thread)
    (main,) = by_name[reduction.WINDOW]
    for name in ("dispatch", "block"):
        assert len(by_name.get(name, ())) == len(body.stamps), name
        assert main not in by_name[name], name
