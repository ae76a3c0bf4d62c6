"""Readings that set ``check.py``'s limits: the program and the control, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds 5

In one process, for each seed, a short window of the cell at its own size
through the timed path, then the same comparison a run makes: first with the
app's float32 body (the lower reading), then with the body rounded to
bfloat16 in its place (the control, the upper reading).  One line per seed;
the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(cell, devices, seeds, seconds, tile=None):
    import check

    app, schedule, runner = run.build(cell, devices, tile)
    run.warm(app, cell.config, runner)
    cfg = cell.config

    def reference(view):
        return app.reference(view, cfg["N"], cfg["width"], cfg["threshold"])

    for seed in seeds:
        win = run.measure(runner, run.frames_of(app, cfg, seed), seconds, seed)
        checks = check.compare(win.kept, reference, schedule, cfg["N"])
        yield seed, len(win.times), win.failed, {k: v for k, (v, _) in checks.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.chips(cell.chips)
    run.use_compile_cache()
    import jax.numpy as jnp

    app = __import__(f"apps.{cell.config['app']}", fromlist=["tile_fn"])
    bf16 = app.tile_fn(cell.config["width"], cell.config["threshold"], jnp.bfloat16)
    for label, seeds, tile in (("program", args.seeds, None),
                               ("control_bf16", args.control_seeds, bf16)):
        for seed, frames, failed, checks in readings(
                cell, devices, [int(s) for s in seeds.split(",")], args.seconds, tile):
            print(label, cell.name, f"seed={seed}", f"frames={frames}", f"failed={failed}",
                  " ".join(f"{k}={v}" for k, v in checks.items()), flush=True)


if __name__ == "__main__":
    main()
