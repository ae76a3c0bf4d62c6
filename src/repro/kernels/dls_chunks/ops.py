"""Public jit'd wrapper around the dls_chunks Pallas kernel."""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import drain_steps
from repro.core.techniques import DLSParams
from repro.core.techniques_jnp import TECH_IDS, default_head_cap, pack_params

from .kernel import TILE, dls_chunks_pallas


def _default_max_steps(technique: str, params: DLSParams) -> int:
    """Smallest step count that drains the loop, from the closed-form prefix.

    The f64 host prefix tells us where cumulative assignment reaches N; a one
    tile margin absorbs any f32-vs-f64 boundary drift (the drift is at most a
    handful of steps, never a whole 1024-step tile).
    """
    upper = int(math.ceil(params.N / max(params.min_chunk, 1)))
    return min(drain_steps(technique, params) + TILE, upper)


def _platform() -> str:
    """Platform of the device an eager call lands on (``jax.default_device``
    if one is set, else the default backend)."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def kernel_args(technique: str, params: DLSParams, max_steps: int | None = None):
    """Static arguments of ``dls_chunks_pallas`` for one schedule:
    (tech_id, pv_tuple, num_tiles, head_cap)."""
    if max_steps is None:
        max_steps = _default_max_steps(technique, params)
    num_tiles = max(int(math.ceil(max_steps / TILE)), 1)
    head_cap = default_head_cap(technique, params, num_tiles * TILE)
    with jax.ensure_compile_time_eval():  # static even under an outer jit
        pv_tuple = tuple(float(x) for x in np.asarray(pack_params(params)))
    return TECH_IDS[technique], pv_tuple, num_tiles, head_cap


def dls_chunk_schedule(
    technique: str,
    params: DLSParams,
    max_steps: int | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute the full DCA schedule on-device.

    Returns (sizes, offsets) int32 [S_padded] in step order; entries with
    size 0 are past the end of the loop.  The platform of the device the call
    lands on decides how the kernel runs: compiled by Mosaic on a TPU, through
    the Pallas interpreter anywhere else.
    """
    tech_id, pv_tuple, num_tiles, head_cap = kernel_args(technique, params, max_steps)
    sizes, offsets = dls_chunks_pallas(
        tech_id, pv_tuple, num_tiles, head_cap=head_cap,
        interpret=_platform() != "tpu",
    )
    return sizes.reshape(-1), offsets.reshape(-1)
