"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``: the app, the loop's size, the path) under a
traffic mix (``traffic/<traffic>.json``: technique, mode, injected delay).
The run, in this one process:

1. finds the cell's chips, and exits non-zero with no result when JAX has
   no TPU or too few chips;
2. keeps compiled programs in ``.jax_cache`` at the checkout's root;
3. builds the path (``paths/<path>.py``) around the app's device body
   (``apps/<app>.py``), renders one frame to warm every program, and more
   until ``WARM_CHUNKS`` chunks have run, to bring the host path to its
   steady pace;
4. renders frames back to back for ``--seconds`` (``--trace 1``: at most
   ``TRACE_SECONDS`` or ``TRACE_FRAMES``, under the profiler);
5. compares a sample of the frames, drawn from the seed, with the app's
   plain reference and the reference schedule (``check.py``); a traced
   run reduces its trace with ``reduction.py``;
6. prints, last on stdout, one JSON line: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics, each read by ``metrics/<name>.py``),
   ``device``, ``breakdown`` when traced, and ``checks`` last.

A frame is one instance of the loop over a viewport that the seed draws.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, for the runtime's start-up time

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import check  # noqa: E402

KEEP = 4  # frames per run compared with the reference
# chunks that set-up runs before the window: a fresh process's host path
# slows each of its first ~30 FAC2 frames (~10**5 chunks), by 25% at first,
# working and not waiting; a sleep of as long leaves it so (PERF.md)
WARM_CHUNKS = 100_000
TRACE_SECONDS, TRACE_FRAMES = 2.0, 16
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses")


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic, and the metrics (with units) that it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    w = cells[name]

    def mine(metrics):
        return {m["name"]: m["unit"] for m in metrics if name in m.get("workloads", [name])}

    return SimpleNamespace(
        name=name, chips=w["chips"],
        config=json.loads((HERE / "configs" / f"{w['config']}.json").read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=mine(bench["end_to_end"]), per_layer=mine(bench["per_layer"]))


def chips(n: int):
    """The first ``n`` TPU devices; exits non-zero where there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU (JAX's devices are {devices[0].platform}); nothing was run")
    if len(devices) < n:
        sys.exit(f"run.py: the cell needs {n} chips, JAX has {len(devices)}")
    return devices[:n]


def use_compile_cache():
    import jax

    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    # no eviction: an evicting cache also writes an access-time file per
    # entry, which failed on the chip's host and left every run compiling
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(cell, devices, tile=None):
    """The app, the reference schedule and the path's runner of ``cell``;
    ``tile`` replaces the app's body (the control, the tests' faults)."""
    cfg, traffic = cell.config, cell.traffic
    app = importlib.import_module(f"apps.{cfg['app']}")
    sched = importlib.import_module(f"schedules.{traffic['technique']}")
    schedule = sched.sizes(cfg["N"], cfg["P"], traffic["mode"])
    path = importlib.import_module(f"paths.{cfg['path']}")
    if tile is None:
        tile = app.tile_fn(cfg["width"], cfg["threshold"])
    runner = path.Runner(cfg, traffic, devices, tile, app.TILE, schedule)
    return app, schedule, runner


def frames_of(app, cfg, seed: int):
    return app.viewports(seed, cfg["width"], cfg["window"], cfg["jitter"])


def warm(app, cfg, runner, chunks: int = 0) -> int:
    """Frames over the configuration's own window until ``chunks`` chunks
    have run, one at least: the first compiles and warms every program the
    window will call.  Returns the number of frames."""
    xa, xb, ya, yb = cfg["window"]
    w = cfg["width"]
    view = np.asarray([xa, ya, (xb - xa) / (w - 1), (yb - ya) / (w - 1)], np.float32)
    frames = done = 0
    while frames == 0 or done < chunks:
        done += len(runner.frame(view).chunks())
        frames += 1
    return frames


def measure(runner, views, seconds: float, seed: int, traced=False, max_frames=None):
    """Frames back to back until ``seconds`` have passed (or ``max_frames``).

    Returns the per-frame times, the window's length (to the end of its
    last frame), the number of incomplete frames, the sample of ``KEEP``
    frames drawn from ``seed`` (reservoir sampling over all frames), and,
    when ``traced`` (with the path's spans), every frame."""
    pick = random.Random(seed)
    times, kept, every, failed = [], [], [], 0
    t0 = t = time.perf_counter()
    while t - t0 < seconds and (max_frames is None or len(times) < max_frames):
        frame = runner.frame(next(views), traced=traced)
        now = time.perf_counter()
        times.append(now - t)
        t = now
        failed += not frame.complete
        i = len(times) - 1
        if i < KEEP:
            kept.append(frame)
        elif (j := pick.randrange(i + 1)) < KEEP:
            kept[j] = frame
        if traced:
            every.append(frame)
    return SimpleNamespace(times=times, window_s=t - t0, failed=failed, kept=kept, frames=every)


class CompileCounter:
    """Counts JAX traces and compiles while ``on``, and persistent-cache
    hits and misses throughout."""

    def __init__(self):
        import jax

        self.on, self.count, self.cache = False, 0, dict.fromkeys(CACHE_EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1

    def _event(self, event, **_):
        if event in self.cache:
            self.cache[event] += 1


def end_to_end(win, setup_s: float) -> dict:
    return {"loop_s": win.window_s / len(win.times),
            "loop_p90_s": float(np.percentile(win.times, 90)),
            "setup_s": setup_s}


def per_layer(names, win, reduced, n_chips) -> dict:
    run = SimpleNamespace(frames=win.frames, trace=reduced, chips=n_chips)
    out = {}
    for name in names:
        value = importlib.import_module(f"metrics.{name}").read(run)
        if value is not None:
            out[name] = value
    return out


def traced(runner, views, seconds, seed, span_names):
    """``measure`` under the profiler, inside a ``window`` span; returns the
    window and the reduced trace."""
    import jax

    import reduction

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="chip-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(reduction.WINDOW):
                win = measure(runner, views, min(seconds, TRACE_SECONDS), seed,
                              traced=True, max_frames=TRACE_FRAMES)
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(tmp).rglob("*.xplane.pb")
        return win, reduction.reduce(reduction.load(str(path), span_names))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(cell, devices, seed: int, seconds: float, trace: bool, tile=None, log=None,
        t_ready=None, warm_chunks: int = WARM_CHUNKS) -> dict:
    """One run of ``cell`` on ``devices``: set-up, window, comparison.
    Returns the result line as a dict, ``checks`` last.

    ``setup_s`` counts from ``t_ready``, when JAX has the chips (default:
    now): the TPU runtime's own start before it (8.5-16 s on a v5e host,
    varying from run to run) is neither the benchmark's nor the program's,
    and is logged apart."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    t_ready = time.perf_counter() if t_ready is None else t_ready
    cfg = cell.config
    counter = CompileCounter()
    t_build = time.perf_counter()
    app, schedule, runner = build(cell, devices, tile)
    t_warm = time.perf_counter()
    warm(app, cfg, runner)
    t_settle = time.perf_counter()
    settle_frames = warm(app, cfg, runner, warm_chunks)
    views = frames_of(app, cfg, seed)
    t_window = time.perf_counter()
    setup_s = t_window - t_ready
    hits, misses = counter.cache.values()
    log(f"setup_s {setup_s:.3f}: to_build {t_build - t_ready:.3f} build {t_warm - t_build:.3f} "
        f"warm_frame {t_settle - t_warm:.3f} settle {t_window - t_settle:.3f} "
        f"({settle_frames} frames); compile cache hits {hits} misses {misses}")
    counter.on = True
    if trace:
        win, reduced = traced(runner, views, seconds, seed, runner.spans)
    else:
        win, reduced = measure(runner, views, seconds, seed), None
    counter.on = False
    log(f"compiles_in_window {counter.count}")
    third = max(1, len(win.times) // 3)
    log("frame_s by thirds of the window " + " ".join(
        f"{np.mean(win.times[k:k + third]):.4f}" for k in (0, third, 2 * third)
        if win.times[k:k + third]))
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes":
              max(s.get("peak_bytes_in_use", 0) for s in stats)}
    runner = None  # the program's state goes before the reference runs

    def reference(view):
        return app.reference(view, cfg["N"], cfg["width"], cfg["threshold"])

    checks = check.compare(win.kept, reference, schedule, cfg["N"])
    if trace:
        metrics = per_layer(cell.per_layer, win, reduced, len(devices))
        units = cell.per_layer
        device["busy_s"] = float(np.mean(reduced["busy_s"])) if reduced["busy_s"] else 0.0
        device["window_s"] = reduced["window_s"]
    else:
        metrics, units = end_to_end(win, setup_s), cell.end_to_end
    result = {
        "correct": win.failed == 0 and bool(win.kept) and check.passed(checks),
        "attempted": len(win.times),
        "failed": win.failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()
                    if k in units},
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    for name, (value, limit) in checks.items():
        log(f"check {name} {value} limit {limit}")
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = chips(cell.chips)
    t_ready = time.perf_counter()
    print(f"runtime_start_s {t_ready - T_START:.3f}", file=sys.stderr, flush=True)
    use_compile_cache()
    print(json.dumps(run(cell, devices, args.seed, args.seconds, bool(args.trace),
                         t_ready=t_ready)), flush=True)


if __name__ == "__main__":
    main()
