"""Beyond-paper benchmarks: the DLS machinery inside the training framework.

  chunk_calc_scaling — chunk-calculation cost vs P: sequential CCA recursion
                       vs vectorized DCA closed forms vs the Pallas kernel
                       (interpreted off a TPU): the TPU adaptation's
                       headline win.
  data_balance       — token-load imbalance of the DLS data scheduler vs
                       STATIC over a heavy-tailed corpus.
  straggler          — self-scheduled microbatches under a slow host.
  sspmd_roundtrip    — device-level DCA rounds: schedule agreement with host.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.executor import SelfSchedulingExecutor
from repro.core.schedule import build_schedule_cca, build_schedule_dca
from repro.core.techniques import DLSParams
from repro.data import DLSBatchScheduler, SyntheticCorpus
from repro.runtime import StragglerMitigator


def bench_chunk_calc_scaling(emit):
    n = 262_144
    for p in (16, 64, 256, 1024):
        params = DLSParams(N=n, P=p)
        t0 = time.perf_counter()
        cca = build_schedule_cca("gss", params)
        t_cca = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        dca = build_schedule_dca("gss", params)
        t_dca = (time.perf_counter() - t0) * 1e6
        emit(f"chunk_calc/gss/P{p}", t_dca,
             f"cca_us={t_cca:.0f};dca_us={t_dca:.0f};steps={dca.num_steps};"
             f"speedup={t_cca/max(t_dca,1e-9):.1f}x")


def bench_chunk_calc_kernel(emit):
    import jax

    from repro.kernels.dls_chunks import dls_chunk_schedule

    params = DLSParams(N=262_144, P=256)
    t0 = time.perf_counter()
    sizes, offs = dls_chunk_schedule("fac", params)
    dt = (time.perf_counter() - t0) * 1e6
    kept = int((np.asarray(sizes) > 0).sum())
    emit("chunk_calc/pallas_fac", dt,
         f"steps={kept};interpret={jax.default_backend() != 'tpu'}")


def bench_data_balance(emit):
    c = SyntheticCorpus(vocab=1000, n_docs=4000, sigma=1.0, seed=1)
    c.lengths = np.sort(c.lengths)[::-1].copy()  # adversarial order
    for tech in ("static", "gss", "fac", "fiss"):
        s = DLSBatchScheduler(c, n_groups=16, technique=tech)
        t0 = time.perf_counter()
        loads = s.group_token_loads(s.schedule.num_steps // 16)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"data_balance/{tech}", dt,
             f"imbalance={loads.max()/loads.mean()-1:.4f}")


def bench_straggler(emit):
    import time as _t

    for tech in ("static", "fac"):
        m = StragglerMitigator(n_micro=48, n_groups=4, technique=tech)
        t0 = time.perf_counter()
        m.run(lambda i: _t.sleep(0.0005))
        dt = (time.perf_counter() - t0) * 1e6
        done = m.chunks_executed()
        emit(f"straggler/{tech}", dt, f"per_worker={sorted(done.values())}")


def bench_hierarchical(emit):
    """Two-level DCA: global-counter contention vs flat self-scheduling."""
    from repro.core.hierarchical import HierarchicalExecutor

    n = 100_000
    for groups, wpg in ((8, 8), (16, 16)):
        ex = HierarchicalExecutor(n, groups, wpg, "gss", "fac")
        t0 = time.perf_counter()
        ex.run(lambda lo, hi: None)
        dt = (time.perf_counter() - t0) * 1e6
        emit(f"hierarchical/g{groups}x{wpg}", dt,
             f"global_claims={ex.global_contention_events};"
             f"flat_claims_equiv={n};chunks={len(ex.records)}")


def bench_executor_modes(emit):
    """CCA vs DCA thread executor under injected calc delay (the paper's
    experiment, real threads instead of simulation)."""
    n, w = 2_000, 8
    for mode in ("cca", "dca"):
        for delay in (0.0, 2e-4):
            ex = SelfSchedulingExecutor("fsc", DLSParams(N=n, P=w), mode=mode,
                                        calc_delay_s=delay)
            t = ex.run(lambda lo, hi: None, n_workers=w)
            emit(f"executor/{mode}/delay{int(delay*1e6)}us", t * 1e6,
                 f"wall_s={t:.4f}")
