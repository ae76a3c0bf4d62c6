"""Drive the scheduler's device path once on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip: schedule, rounds, train
    python3 chip_smoke.py --chips 4   # four chips: the SPMD rounds alone, P=4

The phases run in this one process (a TPU belongs to one process at a time),
in this order, and the first fault ends the run with a non-zero exit:

  schedule  ``dls_chunk_schedule``, the Pallas chunk kernel compiled by Mosaic,
            for the twelve closed-form techniques at the paper's Table 2 size
            (N=1,000, P=4) and Table 4 size (N=262,144, P=256), and for gss
            and ss at the kernel's limit N=2**23.  Each schedule covers
            [0, N) with every iteration once.  It equals the float64 host
            builder ``build_schedule_dca`` in every step at Table 2 size and in
            its first 64 steps at the larger sizes, for every technique but
            rnd (its device hash is 32-bit, the host's 64-bit).  Each line
            also counts the steps whose sizes differ from the Pallas
            interpreter's run of the same kernel on the host CPU, and from
            the host builder over the whole schedule.
  rounds    the SPMD self-scheduling rounds of ``repro.core.sspmd``: the DCA
            rounds (stateless and scanned) and the CCA baseline, for gss and
            fac, under ``jax.shard_map`` on a mesh of the process's devices,
            at N=262,144 with enough rounds to drain the loop.  Each schedule
            covers [0, N) once and its first 64 steps are the host
            builder's (DCA or CCA); the two DCA forms give one chunk
            sequence.  Each line counts the steps that differ from the host
            builder over the whole schedule.
  train     ten steps of the ~125M-parameter repro-100m
            (examples/train_100m.py) through ``repro.launch.train.train``,
            fed by the DLS data scheduler (fac, dca mode), batch 8, seq 256.
            The losses are finite, the first is near ln(vocab) and the last
            is below the first; no step fails and is retried.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero before any phase and prints no result.  Where
JAX_COMPILATION_CACHE_DIR is unset, compiled programs are cached in
``.jax_cache`` at the repository root.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

TABLE2 = (1_000, 4)
TABLE4 = (262_144, 256)
MAX_N = 2 ** 23
HEAD = 64  # steps compared with the host builder at large N, as the CPU tests do


def require(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


def _verify(what, n, sizes, offsets):
    """Every iteration of [0, N) exactly once, in step order, with zero-size
    (drained) steps only at the end.  Returns the live steps' (sizes, offsets)."""
    keep = sizes > 0
    require(keep[: keep.sum()].all(), f"{what}: a drained step precedes a live one")
    s, o = sizes[keep].astype(np.int64), offsets[keep].astype(np.int64)
    require(o[0] == 0 and np.array_equal(o[1:], np.cumsum(s)[:-1]) and s.sum() == n,
            f"{what}: the chunks do not cover [0, {n}) exactly once")
    return s, o


def _host_diff(sizes, host_sizes) -> int:
    """Steps whose size differs from the host builder's, counting extra steps."""
    m = min(len(sizes), len(host_sizes))
    return int((sizes[:m] != host_sizes[:m]).sum()) + abs(len(sizes) - len(host_sizes))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    for x in out:
        x.block_until_ready()
    return out, time.perf_counter() - t0


def phase_schedule(jax):
    from repro.core.schedule import build_schedule_dca
    from repro.core.techniques import DLSParams
    from repro.core.techniques_jnp import TECH_NAMES_DCA
    from repro.kernels.dls_chunks import dls_chunk_schedule

    # the default call on a TPU is the Mosaic kernel, not the interpreter
    params = DLSParams(N=TABLE4[0], P=TABLE4[1])
    lowered = jax.jit(lambda: dls_chunk_schedule("gss", params)).lower()
    require("tpu_custom_call" in lowered.as_text(),
            "schedule: dls_chunk_schedule did not lower to the compiled kernel")
    compiled = lowered.compile()
    _timed(compiled)
    _, jitted_s = _timed(compiled)
    print(f"schedule gss N={params.N} P={params.P} jitted_call_s={jitted_s:.6f}", flush=True)

    cpu = jax.devices("cpu")[0]
    cases = [(t, *TABLE2) for t in TECH_NAMES_DCA]
    cases += [(t, *TABLE4) for t in TECH_NAMES_DCA]
    cases += [("gss", MAX_N, 256), ("ss", MAX_N, 256)]
    for tech, n, p in cases:
        params = DLSParams(N=n, P=p)
        (sizes, offs), first_s = _timed(lambda: dls_chunk_schedule(tech, params))
        _, second_s = _timed(lambda: dls_chunk_schedule(tech, params))
        sizes, offs = np.asarray(sizes), np.asarray(offs)
        with jax.default_device(cpu):
            cpu_sizes = np.asarray(dls_chunk_schedule(tech, params)[0])
        s, o = _verify(f"{tech} N={n}", n, sizes, offs)
        cpu_diff = int((sizes != cpu_sizes).sum())
        line = (f"schedule {tech:6s} N={n} P={p} steps={len(s)} "
                f"first_call_s={first_s:.4f} second_call_s={second_s:.6f} "
                f"cpu_interpret_diff={cpu_diff}")
        if tech != "rnd":
            host = build_schedule_dca(tech, params)
            line += f" host_diff={_host_diff(s, host.sizes)}"
            head = len(s) if (n, p) == TABLE2 else HEAD
            require(np.array_equal(s[:head], host.sizes[:head])
                    and np.array_equal(o[:head], host.offsets[:head]),
                    f"{tech} N={n}: the first {head} steps differ from the host builder")
        print(line, flush=True)


def phase_rounds(jax, devices, p: int):
    from jax.sharding import AxisType, PartitionSpec

    from repro.core.schedule import build_schedule_cca, build_schedule_dca
    from repro.core.sspmd import cca_schedule_scan, dca_schedule_scan, dca_schedule_stateless
    from repro.core.techniques import DLSParams

    n_dev = len(devices)
    mesh = jax.make_mesh((n_dev,), ("pe",), axis_types=(AxisType.Explicit,), devices=devices)
    params = DLSParams(N=TABLE4[0], P=p)
    for tech in ("gss", "fac"):
        host = {"dca": build_schedule_dca(tech, params), "cca": build_schedule_cca(tech, params)}
        forms = (("dca_stateless", dca_schedule_stateless, "dca"),
                 ("dca_scan", dca_schedule_scan, "dca"),
                 ("cca_scan", cca_schedule_scan, "cca"))
        dca_chunks = None
        for name, schedule_fn, mode in forms:
            # one step per device per round; the margin covers f32 drift
            rounds = math.ceil((host[mode].num_steps + HEAD) / n_dev)

            def per_device(fn=schedule_fn, r=rounds):
                offs, sizes = fn(tech, params, "pe", max_rounds=r)
                return offs[None], sizes[None]

            spec = PartitionSpec("pe")
            step = jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(),
                                         out_specs=(spec, spec), check_vma=False))
            t0 = time.perf_counter()
            compiled = step.lower().compile()
            compile_s = time.perf_counter() - t0
            (offs, sizes), run_s = _timed(compiled)
            # [device, round] -> step order (step = round * n_dev + device)
            offs, sizes = (np.asarray(x).T.reshape(-1) for x in (offs, sizes))
            s, o = _verify(f"{name}/{tech}", params.N, sizes, offs)
            require(np.array_equal(s[:HEAD], host[mode].sizes[:HEAD]),
                    f"{name}/{tech}: the first {HEAD} steps differ from the host builder")
            if mode == "dca":
                if dca_chunks is None:
                    dca_chunks = (s, o)
                require(np.array_equal(s, dca_chunks[0]) and np.array_equal(o, dca_chunks[1]),
                        f"{name}/{tech}: the DCA forms give different chunk sequences")
            print(f"rounds {name:13s} {tech} N={params.N} P={p} devices={n_dev} "
                  f"rounds={rounds} steps={len(s)} compile_s={compile_s:.3f} "
                  f"run_s={run_s:.6f} host_diff={_host_diff(s, host[mode].sizes)}",
                  flush=True)


def phase_train():
    from train_100m import config_100m

    from repro.launch.train import train

    class _Retries(logging.Handler):
        def __init__(self):
            super().__init__(logging.WARNING)
            self.records = []

        def emit(self, record):
            self.records.append(record)

    retries = _Retries()
    logging.getLogger("repro.runtime.failure").addHandler(retries)
    cfg = config_100m()
    print(f"train {cfg.name}: {cfg.param_count() / 1e6:.1f}M params", flush=True)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        _, hist = train(cfg, steps=10, batch=8, seq=256, ckpt_dir=ckpt_dir,
                        technique="fac", log_every=1)
    losses = np.array([m["loss"] for m in hist])
    require(not retries.records, f"train: steps failed and were retried: "
            f"{[r.getMessage() for r in retries.records]}")
    require(len(losses) == 10 and np.isfinite(losses).all(), f"train: losses {losses}")
    require(abs(losses[0] - math.log(cfg.vocab)) < 0.5,
            f"train: first loss {losses[0]:.4f}, ln(vocab) = {math.log(cfg.vocab):.4f}")
    require(losses[-1] < losses[0], f"train: loss did not fall: {losses}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the rounds phase, on a 4-device mesh with P=4")
    args = ap.parse_args()

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's devices are {dev.platform}); "
                 f"nothing was run")
    require(len(devices) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, JAX has {len(devices)}")
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}, jax {jax.__version__}",
          flush=True)

    if args.chips == 4:
        phases = [("rounds", lambda: phase_rounds(jax, devices[:4], 4))]
    else:
        phases = [("schedule", lambda: phase_schedule(jax)),
                  ("rounds", lambda: phase_rounds(jax, devices, TABLE4[1])),
                  ("train", phase_train)]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"phase {name} ok wall_s={time.perf_counter() - t0:.2f}", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
