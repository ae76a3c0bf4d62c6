"""repro.core — the paper's contribution: DLS chunk calculation, CCA vs DCA.

Layers:
  techniques      host closed forms (DCA) + recursions (CCA), float64-exact
  techniques_jnp  the same closed forms in jnp (jit/shard_map/Pallas-safe)
  schedule        full-schedule builders + coverage invariants
  source          the ChunkSource protocol — the ONE scheduling API (Static /
                  CriticalSection / Adaptive / Hierarchical backends)
  simulator       discrete-event CCA/DCA comparison with delay injection
  executor        thread-based self-scheduling runtime (LB4MPI analogue)
  device_body     the device-call layer: a chunk as tile calls of a device
                  body, each waited for
  hierarchical    two-level DCA (the paper's HDSS-style companion scheme)
  sspmd           device-level BSP self-scheduler under shard_map
  api             LB4MPI-compatible facade (Listing 1 of the paper)
"""

from .techniques import (
    ADAPTIVE_TECHNIQUES,
    AWFFeedback,
    DLSParams,
    TECHNIQUES,
    closed_form_sizes,
    get_technique,
    technique_names,
)
from .schedule import (
    Schedule,
    build_schedule_cca,
    build_schedule_dca,
    chunk_of_step,
    verify_coverage,
)
from .source import (
    AdaptiveSource,
    Chunk,
    ChunkSource,
    CriticalSectionSource,
    HierarchicalSource,
    ScheduleSpec,
    StaticSource,
    make_source,
    materialize,
    resolve_mode,
    source_for,
)
from .simulator import SimConfig, SimResult, simulate, mandelbrot_costs, psia_costs, constant_costs
from .executor import SelfSchedulingExecutor
from .hierarchical import HierarchicalExecutor
from . import api, sspmd, techniques_jnp

__all__ = [
    "DLSParams", "TECHNIQUES", "ADAPTIVE_TECHNIQUES", "AWFFeedback",
    "get_technique", "closed_form_sizes", "technique_names",
    "Schedule", "build_schedule_cca", "build_schedule_dca", "chunk_of_step", "verify_coverage",
    "Chunk", "ChunkSource", "ScheduleSpec", "StaticSource", "CriticalSectionSource",
    "AdaptiveSource", "HierarchicalSource", "make_source", "source_for",
    "resolve_mode", "materialize",
    "SimConfig", "SimResult", "simulate", "mandelbrot_costs", "psia_costs", "constant_costs",
    "SelfSchedulingExecutor", "HierarchicalExecutor", "api", "sspmd", "techniques_jnp",
]
