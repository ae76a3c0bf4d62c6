"""The Mandelbrot body against its plain reference, and the reference schedules
against the program's own schedule functions, on the CPU."""

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from apps import mandelbrot  # noqa: E402
from schedules import fac, static  # noqa: E402

WIDTH, THRESHOLD = 64, 16
# XLA's CPU backend fuses a * b + c into one rounding (a v5e and numpy do
# not), which can move a pixel on the set's boundary by a count or two; the
# first view of these seeds has no such pixel at this size (seed 3 has one)
SEEDS = (1, 2**31 + 12345)


def _frame(tile, view):
    import jax.numpy as jnp

    n = WIDTH * WIDTH
    got = np.zeros(n, np.int32)
    for lo in range(0, n, 700):  # chunks that straddle tiles, and a short last one
        size = min(700, n - lo)
        res = np.asarray(tile(np.array([lo, size], np.int32), jnp.asarray(view))).reshape(-1)
        assert not res[size:].any(), "masked lanes must read 0"
        got[lo:lo + size] = res[:size]
    return got


@pytest.mark.parametrize("seed", SEEDS)
def test_kernel_equals_reference(seed):
    view = next(mandelbrot.viewports(seed, WIDTH, (-1.5, 1.5, -1.5, 1.5),
                                     {"shift": 0.02, "zoom": 0.01}))
    ref = mandelbrot.reference(view, WIDTH * WIDTH, WIDTH, THRESHOLD)
    assert 0 < ref.mean() < THRESHOLD  # the frame holds both escaping and interior pixels
    assert np.array_equal(_frame(mandelbrot.tile_fn(WIDTH, THRESHOLD), view), ref)


def test_bfloat16_body_fails_the_comparison():
    import jax.numpy as jnp

    view = next(mandelbrot.viewports(SEEDS[0], WIDTH, (-1.5, 1.5, -1.5, 1.5),
                                     {"shift": 0.02, "zoom": 0.01}))
    ref = mandelbrot.reference(view, WIDTH * WIDTH, WIDTH, THRESHOLD)
    got = _frame(mandelbrot.tile_fn(WIDTH, THRESHOLD, jnp.bfloat16), view)
    assert np.count_nonzero(got != ref) > 20


def test_reference_is_listing_3():
    """Escape counts of a few points worked out by hand under z <- z**4 + c."""
    view = np.asarray([-2.0, 0.0, 1.0, 1.0], np.float32)  # pixels -2, -1, 0, 1 (+0i)
    counts = mandelbrot.reference(view, 4, 4, 8)
    # -2: z1 = -2, |z| = 2 escapes at once; -1: z cycles 0, -1, 0 ...; 0: stays 0;
    # 1: z1 = 1, z2 = 2 escapes
    assert counts.tolist() == [0, 8, 8, 1]


def test_viewports_repeat_per_seed_and_stay_near_the_window():
    def take(seed, k=50):
        it = mandelbrot.viewports(seed, 512, (-1.5, 1.5, -1.5, 1.5), {"shift": 0.02, "zoom": 0.01})
        return np.stack([next(it) for _ in range(k)])

    a, b = take(2**31 + 7), take(2**31 + 7)
    assert np.array_equal(a, b) and not np.array_equal(a, take(8))
    assert np.all(np.abs(a[:, 0] + 1.5) <= 1.5 * 0.03 + 1e-6)
    assert np.allclose(a[:, 2], 3.0 / 511, rtol=0.011)


@pytest.mark.parametrize("n,p", [(262_144, 256), (1_000, 4), (12_345, 7)])
def test_reference_schedules_match_the_program(n, p):
    from repro.core.schedule import build_schedule_cca, build_schedule_dca
    from repro.core.techniques import DLSParams

    params = DLSParams(N=n, P=p)
    assert fac.sizes(n, p, "dca") == build_schedule_dca("fac", params).sizes.tolist()
    assert fac.sizes(n, p, "cca") == build_schedule_cca("fac", params).sizes.tolist()
    assert static.sizes(n, p, "dca") == build_schedule_dca("static", params).sizes.tolist()


def test_table4_chunk_counts():
    assert len(fac.sizes(262_144, 256, "dca")) == 2_816
    assert max(fac.sizes(262_144, 256, "dca")) == 512
    assert static.sizes(262_144, 256, "dca") == [1_024] * 256
