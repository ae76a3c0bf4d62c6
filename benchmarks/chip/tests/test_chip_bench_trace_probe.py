"""``trace_probe.py`` on the CPU at a tiny size: its warm-up lines carry the
program's readings, and its windows rotate through off, on and profiled."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import run  # noqa: E402
import trace_probe  # noqa: E402

SEED = 2**31 + 777


def tiny(name):
    cell = run.load_cell(name)
    cell.config.update(N=4096, width=64, threshold=16, P=16)
    return cell


@pytest.fixture(scope="module")
def lines():
    import jax

    out = {}
    for name in ("mandelbrot-t4.fac-dca", "mandelbrot-t4.fac-cca-slow100"):
        saved, run.WARM_CHUNKS = run.WARM_CHUNKS, 64
        try:
            out[name] = list(trace_probe.probe(tiny(name), jax.devices()[:1], SEED,
                                               warm_frames=2, rounds=3, seconds=0.2))
        finally:
            run.WARM_CHUNKS = saved
    return out


@pytest.mark.parametrize("name", ["mandelbrot-t4.fac-dca", "mandelbrot-t4.fac-cca-slow100"])
def test_warm_lines_carry_the_programs_readings(lines, name):
    warm = [ln for ln in lines[name] if ln["phase"] == "warm"]
    assert [ln["frame"] for ln in warm] == [0, 1]
    for ln in warm:
        assert ln["frame_s"] > 0 and ln["gc_s"] >= 0 and len(ln["gc_collections"]) == 3
        for key in ("claim_us", "run_cpu_us", "chunk_exec_us", "lock_wait_us"):
            assert ln[key] is not None and ln[key] >= 0, key
        json.dumps(ln)
    if name.endswith("dca"):
        assert all(ln["lock_wait_us"] == 0.0 for ln in warm)


def test_windows_rotate_and_read_cpu_only_where_tracing_was_on(lines):
    windows = [ln for ln in lines["mandelbrot-t4.fac-dca"] if ln["phase"] == "window"]
    assert [ln["mode"] for ln in windows] == ["off", "on", "profiler", "on", "profiler", "off",
                                              "profiler", "off", "on"]
    for ln in windows:
        assert ln["frames"] >= 1 and ln["loop_s"] > 0
        assert ln["claim_us"] > 0 and ln["chunk_exec_us"] > 0
        for key in ("run_cpu_us", "dispatch_us", "block_us"):
            assert (ln[key] is None) == (ln["mode"] == "off"), key
    profiled = [ln for ln in windows if ln["mode"] == "profiler"]
    # the CPU has no device plane: the whole window is one idle stretch,
    # named by the spans open at its midpoint
    assert all(ln["device_idle_share"] is None and ln["idle_gaps"] for ln in profiled)
