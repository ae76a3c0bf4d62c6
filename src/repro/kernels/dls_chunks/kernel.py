"""Pallas TPU kernel: vectorized DLS chunk-schedule computation.

The paper's DCA makes every chunk size a pure function of its step index; the
analytic schedule engine pushes that one level further: the cumulative chunk
*offset* is also a pure function of the step index (``prefix_for_steps``, the
closed-form prefix contract of DESIGN.md Sec. 7).  On TPU this makes the
whole schedule a data-parallel map over step indices:

  grid step b handles a (ROWS x 128) tile of scheduling steps:
    1. chunk calculation — evaluate the technique's closed form on the tile
       (VPU elementwise math, steps laid out over sublanes x lanes);
    2. chunk assignment — the tile's base offset comes from the closed-form
       prefix evaluated at the tile's first step, plus a within-tile
       exclusive prefix sum.  No state crosses tiles, so the grid is
       **fully parallel** (``dimension_semantics=("parallel",)``): tiles may
       execute in any order or concurrently, which is the kernel-level
       analogue of the paper's coordinator-free chunk assignment.

Earlier revisions carried the queue head through SMEM scratch across a
sequential grid, and had to saturate the int32 carry at N to survive the
unclamped prefix sums of *increasing* techniques (which capped supported N at
~1e6).  Both the carry and the saturation hack are gone: all tile math is f32
and every quantity that must be exact (anything below the drain point) is an
integer < 2**23, so f32 arithmetic is exact there; past the drain point
values only need to stay >= N, which f32 rounding preserves.  Supported
range: N <= 2**23 (~8.4e6).

Tiles are (8, 128) multiples => VMEM-aligned for the v5e VPU; the technique
id and DLS parameters are Python-static (one compiled kernel per technique,
like one schedule object per loop in LB4MPI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.techniques_jnp import prefix_for_steps, sizes_for_steps

ROWS = 8  # sublanes per tile
LANES = 128  # lanes per tile
TILE = ROWS * LANES  # scheduling steps per grid step

MAX_N = 2 ** 23  # f32-exactness bound for the analytic offsets (see above)


def _inclusive_scan(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum along ``axis`` by log-step shift-and-add.

    Mosaic has no cumsum; each step adds the value ``s`` places back, taken
    from a rotate.  Which way the hardware rotate turns is read from a
    rotated iota rather than assumed: of the two rotates by ``s`` and
    ``n - s``, the one whose iota shows index ``k - s`` at ``k`` is used.
    Every partial sum is a sum of a contiguous run of non-negative
    integers, so it is exact wherever the true prefix is below 2**24.
    """
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        fwd = pltpu.roll(x, s, axis)
        back = pltpu.roll(x, n - s, axis)
        src = pltpu.roll(idx, s, axis)
        prev = jnp.where(src == idx - s, fwd, back)
        x = x + jnp.where(idx >= s, prev, 0.0)
        s *= 2
    return x


def _flat_exclusive_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum of an (ROWS, LANES) tile in row-major order."""
    within_row = _inclusive_scan(x, 1) - x  # exclusive along lanes
    row_totals = jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
    row_prefix = _inclusive_scan(row_totals, 0) - row_totals  # over rows
    return within_row + row_prefix


def _dls_chunks_kernel(sizes_ref, offsets_ref, *, tech_id, pv_tuple, head_cap):
    b = pl.program_id(0)

    # params as *static* numpy scalars (Pallas kernels may not capture traced
    # constants; these fold into the kernel body like LB4MPI's per-loop state)
    pv = tuple(np.float32(x) for x in pv_tuple)
    n_total = np.float32(pv_tuple[0])

    # -- chunk calculation (data-parallel over the tile; the paper's DCA) ----
    rows = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    steps = b * TILE + rows * LANES + cols
    raw = sizes_for_steps(tech_id, steps.astype(jnp.float32), pv)
    raw = jnp.clip(jnp.round(raw), 1.0, n_total)

    # -- chunk assignment: analytic tile base + within-tile prefix sum -------
    # The closed-form prefix replaces the SMEM carry entirely: this tile's
    # base offset is a pure function of its first step index.
    base = prefix_for_steps(
        tech_id, (b * TILE).astype(jnp.float32), pv, head_cap=head_cap
    )
    excl = _flat_exclusive_cumsum(raw)
    starts = base + excl
    sizes = jnp.clip(n_total - starts, 0.0, raw)

    sizes_ref[...] = sizes.astype(jnp.int32)
    offsets_ref[...] = jnp.clip(starts, 0.0, n_total).astype(jnp.int32)


def dls_chunks_pallas(
    tech_id: int,
    pv_tuple: tuple,
    num_tiles: int,
    head_cap: int = 4096,
    interpret: bool = False,
):
    """Build the pallas_call for ``num_tiles`` tiles of TILE scheduling steps.

    Returns (sizes, offsets) as (num_tiles*ROWS, LANES) int32 arrays in
    row-major step order.  ``pv_tuple`` is the packed DLSParams vector as a
    static tuple of floats (see techniques_jnp.pack_params); ``head_cap`` the
    static head length for prefix summation (techniques_jnp.default_head_cap).
    ``interpret`` runs the body through the Pallas interpreter, for hosts
    without a TPU.
    """
    if pv_tuple[0] > MAX_N:
        raise ValueError(
            f"N={int(pv_tuple[0])} exceeds the kernel's f32-exact range "
            f"(N <= {MAX_N}); use the float64 host schedule builder instead"
        )
    kernel = functools.partial(
        _dls_chunks_kernel, tech_id=tech_id, pv_tuple=pv_tuple, head_cap=head_cap
    )
    out_rows = num_tiles * ROWS
    return pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda b: (b, 0)),
            pl.BlockSpec((ROWS, LANES), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),  # stateless tiles => any order
        ),
        interpret=interpret,
        name=f"dls_chunks_tech{tech_id}",
    )()
