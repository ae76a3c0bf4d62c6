"""The host path's device-call layer on the CPU: the path takes the program's
``DeviceBody`` and no copy of its own; a frame ends on the path's own
batched wait over every result, also where the body waits for none; and the
dispatch and block readers take one stamp a tile call from a traced frame.
The body itself is tested in ``tests/test_device_body.py``."""

import importlib
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import check  # noqa: E402
import run  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.device_body import DeviceBody  # noqa: E402

SEED = 2**31 + 12345
HOST_CELLS = ["mandelbrot-t4.fac-dca", "mandelbrot-t4.fac-cca-slow100", "mandelbrot-t4.static-dca"]


@pytest.fixture(scope="module")
def built():
    """``built(name, P)``: the app, reference schedule, runner and view of the
    cell ``name`` at 64 x 64 pixels, threshold 16, ``P`` PEs; built once."""
    import jax

    cache = {}

    def get(name, p=16):
        if (name, p) not in cache:
            cell = run.load_cell(name)
            cell.config.update(N=4096, width=64, threshold=16, P=p)
            app, schedule, runner = run.build(cell, jax.devices()[:1])
            view = next(run.frames_of(app, cell.config, SEED))
            cache[name, p] = SimpleNamespace(app=app, cfg=cell.config, schedule=schedule,
                                             runner=runner, view=view)
        return cache[name, p]
    return get


@pytest.fixture
def waits(monkeypatch):
    """The leaves handed to each ``jax.block_until_ready`` call, in order."""
    import jax

    calls, real = [], jax.block_until_ready

    def wait(x):
        calls.append(jax.tree_util.tree_leaves(x))
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    return calls


def outputs(frame):
    return [out for _, _, out in frame._results]


def test_host_path_takes_the_programs_device_body():
    path = importlib.import_module("paths.executor")
    assert path.DeviceBody is DeviceBody
    assert importlib.util.find_spec("device_body") is None
    assert {"make_source", *tracing.SPANS} == set(path.SPANS)


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", HOST_CELLS)
def test_frame_waits_on_every_result_before_it_returns(built, waits, name, traced):
    b = built(name)
    frame = b.runner.frame(b.view, traced=traced)
    outs = outputs(frame)
    assert len(outs) == len(frame.chunks()) >= 1
    # one batched wait, handed every output once the executor had returned
    assert [[id(a) for a in leaves] for leaves in waits] == [[id(a) for a in outs]]
    assert all(out.is_ready() for out in outs)


class NoWait(DeviceBody):
    """Dispatches every tile of a chunk and waits for none."""

    def __call__(self, lo, hi):
        for a in range(lo, hi, self.tile_size):
            size = min(self.tile_size, hi - a)
            self.results.append((a, size, self.tile(np.array([a, size], np.int32), *self.args)))


@pytest.mark.parametrize("name", HOST_CELLS)
def test_frame_of_a_body_that_does_not_wait_ends_ready_and_correct(built, waits, monkeypatch,
                                                                     name):
    b = built(name)
    monkeypatch.setattr(importlib.import_module("paths.executor"), "DeviceBody", NoWait)
    frame = b.runner.frame(b.view)
    outs = outputs(frame)
    assert [[id(a) for a in leaves] for leaves in waits] == [[id(a) for a in outs]]
    assert all(out.is_ready() for out in outs)

    def reference(view):
        return b.app.reference(view, b.cfg["N"], b.cfg["width"], b.cfg["threshold"])

    checks = check.compare([frame], reference, b.schedule, b.cfg["N"])
    assert check.passed(checks), checks


@pytest.mark.parametrize("name", HOST_CELLS)
@pytest.mark.parametrize("reader,part", [("dispatch_us", 0), ("block_us", 1)])
def test_reader_takes_one_stamp_per_tile_call(built, reader, part, name):
    b = built(name, p=2)  # static's chunks span two tiles at P = 2
    read = importlib.import_module(f"metrics.{reader}").read
    frames = [b.runner.frame(b.view, traced=True) for _ in range(2)]
    for frame in frames:
        calls = sum(-(-size // b.app.TILE) for _, _, size in frame.chunks())
        assert len(frame.stamps) == len(frame.tiles()) == calls
    stamps = [s[part] for frame in frames for s in frame.stamps]
    assert read(SimpleNamespace(frames=frames)) == pytest.approx(1e6 * np.mean(stamps))
    assert read(SimpleNamespace(frames=frames)) > 0
    untraced = b.runner.frame(b.view)
    assert untraced.stamps == [] and read(SimpleNamespace(frames=[untraced])) is None
