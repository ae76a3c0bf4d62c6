"""The harness end to end on the CPU, at a tiny size: the result line, the
refusal without a TPU, and ``correct`` coming out false when the timed path
is broken underneath it.

The four-chip cell needs four devices, which the CPU backend gives only when
told before it starts; its runs go to one child process
(``python test_chip_bench_run.py``) with four virtual devices.
"""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import run  # noqa: E402

SEED = 2**31 + 12345
SECONDS = 0.3
WARM_CHUNKS = 64  # a few tiny frames of set-up after the compiling one
HOST_CELLS = ["mandelbrot-t4.fac-dca", "mandelbrot-t4.fac-cca-slow100", "mandelbrot-t4.static-dca"]
SPMD_CONFIG = "mandelbrot-t4-4chip"  # run under the fac-dca traffic
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def tiny(name, config=None):
    """The cell at 64 x 64 pixels, threshold 16, P = 16: same code, CPU-sized;
    ``config`` puts another configuration under the cell's traffic."""
    cell = run.load_cell(name)
    if config:
        cell.config = json.loads((HERE / "configs" / f"{config}.json").read_text())
        cell.chips = cell.config["chips"]
    cell.config.update(N=4096, width=64, threshold=16, P=16)
    return cell


def _altered(tile):
    """One pixel of the chunk that starts at 0 comes out one count high."""
    import jax.numpy as jnp

    def body(chunk, view):
        out = tile(chunk, view)
        return jnp.where(chunk[0] == 0, out.at[0, 0].add(1), out)
    return body


def _half(tile):
    """Chunks whose offset is an odd multiple of 16 are never computed."""
    import jax.numpy as jnp

    def body(chunk, view):
        return jnp.where((chunk[0] // 16) % 2 == 1, 0, tile(chunk, view))
    return body


def _bf16(tile):
    import jax.numpy as jnp

    from apps import mandelbrot

    return mandelbrot.tile_fn(64, 16, jnp.bfloat16)


def _crash(tile):
    """The body raises on the chunk at 0: its worker dies, its results are lost."""
    def body(chunk, view):
        if int(chunk[0]) == 0:
            raise RuntimeError("lost chunk")
        return tile(chunk, view)
    return body


BODY_FAULTS = {"none": None, "altered": _altered, "half": _half, "bf16": _bf16, "crash": _crash}


def _shifted_source():
    """Step 3's chunk is handed out one iteration late."""
    from repro.core import source

    real = source.make_source

    class Shifted:
        def __init__(self, inner):
            self._inner = inner

        def claim(self, worker=0):
            c = self._inner.claim(worker)
            if c is not None and c.step == 3:
                c = source.Chunk(c.step, c.lo + 1, c.hi + 1, c.worker)
            return c

        def __getattr__(self, attr):
            return getattr(self._inner, attr)

    return source, "make_source", lambda spec, **kw: Shifted(real(spec, **kw))


def _chip_zero_everywhere():
    """Every chip computes chip 0's chunks: the rounds' per-chip step left out."""
    import jax

    from repro.core import sspmd

    real = sspmd.dca_schedule_for_spec

    def same(spec, axis_name, max_rounds=None):
        offs, sizes = real(spec, axis_name, max_rounds)
        return jax.lax.all_gather(offs, axis_name)[0], jax.lax.all_gather(sizes, axis_name)[0]

    return sspmd, "dca_schedule_for_spec", same


PROGRAM_FAULTS = {"shifted_source": _shifted_source, "chip_zero": _chip_zero_everywhere}


def scenario(name, fault="none", trace=False, config=None):
    """One run of the tiny ``name`` with ``fault`` planted; the result line."""
    import jax

    from apps import mandelbrot

    cell = tiny(name, config)
    devices = jax.devices()[:cell.chips]
    tile = mandelbrot.tile_fn(64, 16)
    undo = None
    if fault in PROGRAM_FAULTS:
        module, attr, fake = PROGRAM_FAULTS[fault]()
        undo = (module, attr, getattr(module, attr))
        setattr(module, attr, fake)
    elif BODY_FAULTS[fault]:
        tile = BODY_FAULTS[fault](tile)
    try:
        return run.run(cell, devices, SEED, SECONDS, trace, tile=tile, log=lambda _: None,
                       warm_chunks=WARM_CHUNKS)
    finally:
        if undo:
            setattr(*undo)


@pytest.mark.parametrize("name", HOST_CELLS)
def test_sound_run_is_correct(name):
    res = scenario(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res) == KEYS
    assert set(res["metrics"]) == {"loop_s", "loop_p90_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1


def test_traced_line_has_breakdown_and_layer_metrics():
    res = scenario("mandelbrot-t4.fac-dca", trace=True)
    assert res["correct"]
    assert list(res) == KEYS[:5] + ["breakdown", "checks"]
    # the CPU has no device plane, so device_idle_share finds nothing to read
    assert set(res["metrics"]) == {"source_build_ms", "claim_gap_us", "chunk_exec_us",
                                   "claim_us", "run_cpu_us", "dispatch_us", "block_us"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("name", HOST_CELLS[1:])
def test_traced_line_reads_the_programs_spans_in_every_host_cell(name):
    res = scenario(name, trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == set(run.load_cell(name).per_layer) - {"device_idle_share"}
    assert ("lock_wait_us" in res["metrics"]) == name.endswith("slow100")


@pytest.mark.parametrize("fault,number", [("bf16", "pixels_differing"),
                                          ("altered", "pixels_differing"),
                                          ("half", "pixels_differing"),
                                          ("shifted_source", "iterations_not_once"),
                                          ("shifted_source", "steps_differing")])
@pytest.mark.parametrize("name", ["mandelbrot-t4.fac-dca", "mandelbrot-t4.fac-cca-slow100"])
def test_host_path_fault_is_not_correct(name, fault, number):
    res = scenario(name, fault)
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_lost_results_count_as_failed(monkeypatch):
    monkeypatch.setattr(threading, "excepthook", lambda args: None)
    res = scenario("mandelbrot-t4.fac-dca", "crash")
    assert not res["correct"]
    assert res["failed"] == res["attempted"] >= 1


@pytest.fixture(scope="module")
def spmd_runs():
    """The SPMD cell's scenarios, run in one child with four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


SPMD_FAULTS = [("altered", "pixels_differing"), ("half", "pixels_differing"),
               ("bf16", "pixels_differing"), ("chip_zero", "iterations_not_once"),
               ("chip_zero", "steps_differing")]


def test_spmd_sound_run_is_correct(spmd_runs):
    res = spmd_runs["none"]
    assert res["correct"], res["checks"]
    assert list(res) == KEYS and res["device"]["count"] == 4


@pytest.mark.parametrize("fault,number", SPMD_FAULTS)
def test_spmd_fault_is_not_correct(spmd_runs, fault, number):
    res = spmd_runs[fault]
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", HOST_CELLS[0], "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_sweep_reads_each_worker_count():
    import jax

    import sweep_pes

    rows = list(sweep_pes.sweep(tiny(HOST_CELLS[0]), jax.devices()[:1], [1, 3], SECONDS, SEED))
    assert [r[0] for r in rows] == [1, 3]
    for _, frames, loop_s, gap, hold in rows:
        assert frames >= 1 and loop_s > 0 and gap >= 0 and hold > 0


@pytest.mark.parametrize("chunks", [0, 1, 300])
def test_warm_runs_frames_until_its_chunks_have_run(chunks):
    import jax

    cell = tiny(HOST_CELLS[0])
    app, schedule, runner = run.build(cell, jax.devices()[:1])
    frames = run.warm(app, cell.config, runner, chunks)
    per_frame = len(schedule)
    assert frames == max(1, -(-chunks // per_frame))


def test_loop_metrics_cover_the_whole_window():
    from types import SimpleNamespace

    times = [0.1, 0.2, 0.3, 0.4]
    m = run.end_to_end(SimpleNamespace(times=times, window_s=1.0), 5.0)
    assert m["loop_s"] == pytest.approx(0.25)
    assert m["loop_p90_s"] == pytest.approx(float(np.percentile(times, 90)))
    assert m["setup_s"] == 5.0


if __name__ == "__main__":
    faults = ["none"] + sorted({f for f, _ in SPMD_FAULTS})
    print(json.dumps({f: scenario(HOST_CELLS[0], f, config=SPMD_CONFIG) for f in faults}))
