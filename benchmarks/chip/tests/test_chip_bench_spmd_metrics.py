"""The SPMD cell's per-layer readers on hand-built runs: known busy times,
device ops and chunks give known values, and a run with no trace, one chip
or no frames gives none."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import run  # noqa: E402
from metrics import pe_imbalance, round_overhead_share, round_us  # noqa: E402

CELL = "mandelbrot-t4-4chip.fac-dca-spmd"
READERS = {"pe_imbalance": pe_imbalance, "round_us": round_us,
           "round_overhead_share": round_overhead_share}


class Frame:
    """A frame whose live rounds are ``steps`` (step = round * chips + chip)."""

    def __init__(self, steps):
        self._steps = steps

    def chunks(self):
        return [(s, 10 * s, 10) for s in self._steps]


def _run(busy, ops=(), frames=(), chips=None):
    trace = {"window_s": 1.0, "busy_s": list(busy), "device_ops": [list(o) for o in ops],
             "idle_gaps": []}
    return SimpleNamespace(frames=list(frames), trace=trace,
                           chips=len(busy) if chips is None else chips)


BUSY = [0.012, 0.010, 0.008, 0.010]
OPS = [("while", 0.036), ("mandelbrot_tile", 0.030), ("fusion", 0.004)]
FRAMES = [Frame(range(12)), Frame(range(12))]  # 6 rounds a chip


def test_pe_imbalance_is_the_busiest_chip_over_the_mean():
    assert pe_imbalance.read(_run(BUSY)) == pytest.approx(0.012 / 0.010)
    assert pe_imbalance.read(_run([0.01] * 4)) == pytest.approx(1.0)


def test_round_us_is_the_chips_busy_time_over_their_rounds():
    assert round_us.read(_run(BUSY, OPS, FRAMES)) == pytest.approx(1e6 * 0.040 / 4 / 6)


def test_round_us_counts_only_live_rounds_and_needs_no_chip_order():
    # the last round is live on two chips only: 10 live rounds in all; the
    # sum does not pair a device's busy time with a mesh position
    frames = [Frame(range(10))]
    want = 1e6 * 0.040 / 10
    assert round_us.read(_run(BUSY, OPS, frames)) == pytest.approx(want)
    assert round_us.read(_run(BUSY[::-1], OPS, frames)) == pytest.approx(want)


def test_round_overhead_share_is_busy_time_outside_the_kernel():
    # the ``while`` spans the kernel: busy time is a union, so nothing counts twice
    assert round_overhead_share.read(_run(BUSY, OPS, FRAMES)) == pytest.approx(1 - 0.030 / 0.040)


EMPTY = {
    "no_trace": _run([], chips=4),
    "idle_device": _run([0.0] * 4, OPS, FRAMES),
    "one_chip": _run([0.010], OPS, [Frame(range(6))]),
    "no_frames": _run(BUSY, OPS),  # only round_us reads the frames
}
EMPTY_CASES = [(name, case) for name in sorted(READERS) for case in EMPTY
               if case != "no_frames" or name == "round_us"]


@pytest.mark.parametrize("name,case", EMPTY_CASES)
def test_reader_reads_nothing_where_there_is_nothing_to_read(name, case):
    assert READERS[name].read(EMPTY[case]) is None


def test_round_overhead_share_reads_nothing_without_the_kernel():
    assert round_overhead_share.read(_run(BUSY, [("fusion", 0.02)], FRAMES)) is None


def test_the_cell_reports_all_three():
    cell = run.load_cell(CELL)
    assert cell.chips == 4 and set(READERS) <= set(cell.per_layer)
    win = SimpleNamespace(frames=FRAMES)
    got = run.per_layer(cell.per_layer, win, _run(BUSY, OPS).trace, 4)
    assert set(got) == set(READERS)
