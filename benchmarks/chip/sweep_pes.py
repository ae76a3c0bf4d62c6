"""Frame time against the number of host workers, on the chip.

    python3 benchmarks/chip/sweep_pes.py --workload <cell> --pes 1,2,4,8,16 --seconds 8

In one process, for each worker count: the cell's path is built with that
many workers (the configuration's ``pes``), warmed with one frame, and timed
for ``--seconds``.  One line per count: the frames, the mean frame time
(``loop_s``), and the claim gap and chunk hold (``claim_gap_us``,
``chunk_exec_us``) over a sample of the window's frames.  The sweep is what
the configurations' ``pes`` was chosen from; the benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import importlib
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def sweep(cell, devices, counts, seconds, seed):
    """``(pes, frames, loop_s, claim_gap_us, chunk_exec_us)`` per count."""
    readers = {name: importlib.import_module(f"metrics.{name}")
               for name in ("claim_gap_us", "chunk_exec_us")}
    for pes in counts:
        cell.config["pes"] = pes
        app, _, runner = run.build(cell, devices)
        run.warm(app, cell.config, runner)
        win = run.measure(runner, run.frames_of(app, cell.config, seed), seconds, seed)
        sample = SimpleNamespace(frames=win.kept)
        yield (pes, len(win.times), win.window_s / len(win.times),
               *(reader.read(sample) for reader in readers.values()))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pes", required=True, help="worker counts, comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.chips(cell.chips)
    run.use_compile_cache()
    for pes, frames, loop_s, gap, hold in sweep(
            cell, devices, [int(k) for k in args.pes.split(",")], args.seconds, args.seed):
        print(f"sweep {cell.name} pes={pes} frames={frames} loop_s={loop_s:.6f} "
              f"claim_gap_us={gap:.2f} chunk_exec_us={hold:.2f}", flush=True)


if __name__ == "__main__":
    main()
