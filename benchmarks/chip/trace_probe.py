"""The program's own tracing on the chip: the warm-up, the claim and dispatch
layers' readings, and what tracing costs.

    python3 benchmarks/chip/trace_probe.py --workload <cell> --seed <n> \
        --warm-frames 40 --rounds 6

In one process, with the cell's path and the program's tracing switch
(``repro.core.tracing``):

1. from the first (compiling) frame on, ``--warm-frames`` frames with tracing
   on and no profiler, one line each: the frame's time, ``claim_us``,
   ``lock_wait_us``, ``run_cpu_us``, ``dispatch_us``, ``block_us``,
   ``chunk_exec_us``, ``claim_gap_us``, and the collections (per
   generation) and seconds of GC in it;
2. untraced frames until ``run.WARM_CHUNKS`` chunks have run in all, as a
   run's set-up;
3. ``--rounds`` rounds of three windows of ``--seconds`` each, in an order
   that rotates every round: tracing off, tracing on, and tracing on under
   the profiler as a traced run's window (``run.traced``, with the
   program's spans); one line per window with its frame time (``loop_s``)
   and step 1's readings over its frames (tracing off: those the records
   always hold); a profiled window adds the device's idle share and the
   idle gaps by the spans open in them.

One JSON object per line on stdout.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

READINGS = ("claim_us", "lock_wait_us", "run_cpu_us", "dispatch_us", "block_us", "chunk_exec_us",
            "claim_gap_us")
MODES = ("off", "on", "profiler")


def readings(frames) -> dict:
    sample = SimpleNamespace(frames=frames, trace=None, chips=1)
    return {name: importlib.import_module(f"metrics.{name}").read(sample) for name in READINGS}


def probe(cell, devices, seed, warm_frames, rounds, seconds):
    """Step 1's, then step 3's lines, as dicts."""
    from repro.core import tracing

    cfg = cell.config
    app, _, runner = run.build(cell, devices)
    views = run.frames_of(app, cfg, seed)
    done = 0
    with tracing.on():
        for k in range(warm_frames):
            gc0 = tracing.gc_stats()
            t = time.perf_counter()
            frame = runner.frame(next(views))
            frame_s = time.perf_counter() - t
            gc1 = tracing.gc_stats()
            done += len(frame.records)
            yield {"phase": "warm", "frame": k, "frame_s": frame_s, **readings([frame]),
                   "gc_collections": [b - a for a, b in zip(gc0["collections"],
                                                            gc1["collections"])],
                   "gc_s": gc1["seconds"] - gc0["seconds"]}
    run.warm(app, cfg, runner, max(0, run.WARM_CHUNKS - done))
    for r in range(rounds):
        for mode in MODES[r % 3:] + MODES[:r % 3]:
            line = {"phase": "window", "round": r, "mode": mode}
            if mode == "off":
                win = run.measure(runner, views, seconds, seed)
            else:
                with tracing.on():
                    if mode == "on":
                        win = run.measure(runner, views, seconds, seed)
                    else:
                        win, reduced = run.traced(runner, views, seconds, seed, runner.spans)
            line.update(frames=len(win.times), loop_s=win.window_s / len(win.times),
                        **readings(win.frames or win.kept))
            if mode == "profiler":
                busy = reduced["busy_s"]
                line["device_idle_share"] = (1.0 - busy[0] / reduced["window_s"]
                                             if busy else None)
                line["idle_gaps"] = reduced["idle_gaps"]
            yield line


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--warm-frames", type=int, default=40)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS,
                    help="each window's length (a profiled one stops at run.TRACE_FRAMES)")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.chips(cell.chips)
    run.use_compile_cache()
    for line in probe(cell, devices, args.seed, args.warm_frames, args.rounds, args.seconds):
        print(json.dumps({"cell": cell.name, **line}), flush=True)


if __name__ == "__main__":
    main()
