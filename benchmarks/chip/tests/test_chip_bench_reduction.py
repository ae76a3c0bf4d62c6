"""The trace reduction on a small recorded trace and on hand-made ones.

``fixtures/host_path_trace.json`` is 0.6 ms of a v5e trace of the host path
(FAC2, 4 workers), as ``reduction.load`` reads it, with a ``window`` span
added around it."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]

import reduction  # noqa: E402


def _fixture():
    return json.loads((HERE / "tests" / "fixtures" / "host_path_trace.json").read_text())


def test_recorded_trace_gives_the_known_busy_and_idle_time():
    rec = _fixture()
    out = reduction.reduce(rec)
    assert out["window_s"] == pytest.approx(600e-6)
    assert out["busy_s"] == [pytest.approx(56_635e-9)]
    assert out["device_ops"][0] == ["mandelbrot_tile", pytest.approx(55_723e-9)]
    assert dict(out["idle_gaps"]) == {"body": pytest.approx(540_360e-9),
                                      "block+body": pytest.approx(3_005e-9)}
    # busy and idle fill the window
    idle = sum(v for _, v in out["idle_gaps"])
    assert out["busy_s"][0] + idle == pytest.approx(out["window_s"])


def test_busy_time_equals_a_brute_force_union():
    rec = _fixture()
    (t0, dur, _, _), = [s for s in rec["spans"] if s[2] == "window"]
    mask = np.zeros(dur, bool)
    for a, d, _ in rec["devices"][0]:
        mask[max(a - t0, 0):max(min(a + d - t0, dur), 0)] = True
    assert reduction.reduce(rec)["busy_s"][0] == pytest.approx(mask.sum() / 1e9)


def test_overlaps_clipping_and_labels():
    rec = {"devices": [[[0, 40, "%a.1 = f32[] x"], [20, 40, "%b = f32[] y"],
                        [90, 30, "%a.2 = f32[] x"]],
                       [[10, 10, "%a.3 = f32[] x"]]],
           "spans": [[5, 100, "window", 0], [60, 20, "claim", 1], [65, 5, "body", 2]]}
    out = reduction.reduce(rec)
    # device 0 busy in [5, 60) and [90, 105): 70 ns; device 1: 10 ns
    assert out["busy_s"] == [pytest.approx(70e-9), pytest.approx(10e-9)]
    assert out["window_s"] == pytest.approx(100e-9)
    # one gap [60, 90), midpoint 75: only "claim" is open there
    assert out["idle_gaps"] == [["claim", pytest.approx(30e-9)]]
    # op time inside the window: a = 35 + 15 + 10, b = 40
    assert dict(out["device_ops"]) == {"a": pytest.approx(60e-9), "b": pytest.approx(40e-9)}


def test_op_names():
    assert reduction.op_name("%mandelbrot_tile.1 = s32[8,128]{1,0} custom-call(...)") == \
        "mandelbrot_tile"
    assert reduction.op_name("%copy-start = (f32[4]) copy-start(...)") == "copy-start"
