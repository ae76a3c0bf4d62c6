"""The device-body adapter on the CPU: one call per tile of a chunk, each
waited for, results and stamps as its docstring says, under the executor as
alone; and the host path taking this adapter and no other."""

import contextlib
import importlib
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from device_body import DeviceBody  # noqa: E402
from repro.core import tracing  # noqa: E402

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


def test_untraced_body_opens_no_span_and_stamps_nothing(tile, offset, spans):
    body = DeviceBody(tile, TILE, offset)
    with tracing.on():  # switched on after the body was built: it stays untraced
        body(0, 3000)
    assert spans == [] and body.stamps == [] and len(body.results) == 3


def test_traced_body_opens_both_spans_and_stamps_each_call(tile, offset, spans):
    with tracing.on():
        body = DeviceBody(tile, TILE, offset)
        body(0, 3000)
        body(4000, 4096)
    assert spans == ["dispatch", "block"] * 4
    assert len(body.stamps) == len(body.results) == 4
    assert all(d > 0 and b >= 0 for d, b in body.stamps)


@pytest.mark.parametrize("traced", [False, True])
def test_an_error_in_the_body_reaches_the_caller(traced):
    def tile(chunk, offset):
        raise ValueError(f"no tile at {int(chunk[0])}")

    with tracing.on() if traced else contextlib.nullcontext():
        body = DeviceBody(tile, TILE, 0)
    with pytest.raises(ValueError, match="no tile at 0"):
        body(0, 10)
    assert body.results == [] and body.stamps == []


@pytest.fixture
def fresh_path(monkeypatch):
    """``paths.executor`` imported anew, after the test has set up its modules."""
    monkeypatch.delitem(sys.modules, "paths.executor", raising=False)
    yield lambda: importlib.import_module("paths.executor")
    sys.modules.pop("paths.executor", None)



@pytest.mark.parametrize("program_module", [False, True])
def test_host_path_takes_the_benchmarks_adapter(fresh_path, monkeypatch, program_module):
    """The path's adapter is this benchmark's, also where the program has a
    module of that name: no file outside the benchmark can swap it."""
    program = None
    if program_module:
        program = types.ModuleType("repro.core.device_body")
        program.DeviceBody = type("DeviceBody", (DeviceBody,), {})
    monkeypatch.setitem(sys.modules, "repro.core.device_body", program)
    path = fresh_path()
    assert path.DeviceBody is DeviceBody
    assert {"make_source", "dispatch", "block", *tracing.SPANS} == set(path.SPANS)
