"""The Mandelbrot loop of arXiv:2101.07050 (Listing 3): the benchmark's body.

Iteration ``p`` of the loop is pixel ``(p % width, p // width)`` of a
``width`` x ``width`` frame over a viewport of the complex plane.  Its result
is the escape count under z <- z**4 + c: the number of updates after which
|z| is still below 2, at most ``threshold``.

``tile_fn`` is the device body: one Pallas kernel over one (8, 128) tile of
1,024 consecutive iterations, given ``(lo, size)`` as scalars.  Iterations at
or past ``lo + size`` are masked out and read 0.  The escape loop runs inside
the kernel, in blocks of ``UNROLL`` updates, and stops once every live pixel
of the tile has escaped or reached the threshold, so a tile costs what its
slowest pixel costs: the paper's irregular per-chunk load, on the device.

``reference`` is the plain sequential loop over all pixels, in numpy, with
the same float32 operations in the same order.  ``viewports`` draws the
frame stream from the seed.
"""

from __future__ import annotations

import functools

import numpy as np

ROWS, LANES = 8, 128
TILE = ROWS * LANES
UNROLL = 8  # escape-loop updates between two "any pixel alive?" tests


def _exact(x):
    return x


def _step(zr, zi, cr, ci, q=_exact):
    """One update z <- z**4 + c as (z**2)**2, in the order both sides use.

    ``q`` rounds each result to the working precision (the identity in
    float32)."""
    ar = q(q(zr * zr) - q(zi * zi))
    ai = q(q(zr + zr) * zi)
    nr = q(q(q(ar * ar) - q(ai * ai)) + cr)
    ni = q(q(q(ar + ar) * ai) + ci)
    return nr, ni


def _tile_kernel(chunk_ref, view_ref, out_ref, *, width, threshold, dtype):
    import jax
    import jax.numpy as jnp

    lo = chunk_ref[0]
    size = chunk_ref[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    idx = lo + row * LANES + lane
    live = (idx - lo) < size
    px = jax.lax.rem(idx, jnp.int32(width))
    py = jax.lax.div(idx, jnp.int32(width))
    # values stay float32 (a v5e computes no bfloat16 on its vector unit);
    # a lower ``dtype`` rounds every result to it
    if dtype == jnp.float32:
        q = _exact
    else:
        def q(x):
            return x.astype(dtype).astype(jnp.float32)
    x0, y0, dx, dy = (q(view_ref[k]) for k in range(4))
    cr = q(x0 + q(q(px.astype(jnp.float32)) * dx))
    ci = q(y0 + q(q(py.astype(jnp.float32)) * dy))
    four = jnp.float32(4.0)

    # the loop carries ``alive`` as int32 0/1: Mosaic keeps no bool vector
    # across loop iterations
    def block(carry):
        it, zr, zi, alive, count = carry
        for _ in range(UNROLL):
            nr, ni = _step(zr, zi, cr, ci, q)
            on = alive > 0
            zr = jnp.where(on, nr, zr)
            zi = jnp.where(on, ni, zi)
            alive = jnp.where(on & (q(q(zr * zr) + q(zi * zi)) < four), 1, 0)
            count = count + alive
        return it + UNROLL, zr, zi, alive, count

    def more(carry):
        it, _, _, alive, _ = carry
        return (it < threshold) & (jnp.max(alive) > 0)

    zero = jnp.zeros((ROWS, LANES), jnp.float32)
    init = (jnp.int32(0), zero, zero, jnp.where(live, 1, 0),
            jnp.zeros((ROWS, LANES), jnp.int32))
    out_ref[...] = jax.lax.while_loop(more, block, init)[4]


def tile_fn(width: int, threshold: int, dtype=None, interpret: bool | None = None):
    """The body as a jitted ``tile(chunk, view) -> int32[8, 128]``.

    ``chunk`` is the int32 pair (lo, size), ``view`` the float32 vector
    (x0, y0, dx, dy) of ``viewports``.
    ``dtype`` is the precision of the escape loop (float32 is the
    configuration's; bfloat16 is the control).  ``interpret`` defaults to
    the Pallas interpreter on any platform but a TPU.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if threshold % UNROLL:
        raise ValueError(f"threshold {threshold} is not a multiple of {UNROLL}")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    kernel = functools.partial(_tile_kernel, width=width, threshold=threshold,
                               dtype=dtype or jnp.float32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return jax.jit(pl.pallas_call(
        kernel,
        in_specs=[smem, smem],
        out_shape=jax.ShapeDtypeStruct((ROWS, LANES), jnp.int32),
        interpret=interpret,
        name="mandelbrot_tile",
    ))


def viewports(seed: int, width: int, window, jitter: dict):
    """Endless stream of float32 views (x0, y0, dx, dy), one per frame.

    Each view is the configuration's window shifted by up to
    ``jitter["shift"]`` of its half-width in each direction and zoomed by a
    factor within 1 +- ``jitter["zoom"]``, drawn from ``seed``.
    """
    rng = np.random.default_rng(seed)
    xa, xb, ya, yb = window
    cx, cy, hx, hy = (xa + xb) / 2, (ya + yb) / 2, (xb - xa) / 2, (yb - ya) / 2
    while True:
        sx, sy, z = rng.uniform(-1.0, 1.0, 3)
        zoom = 1.0 + jitter["zoom"] * z
        x0 = cx + jitter["shift"] * hx * sx - hx * zoom
        y0 = cy + jitter["shift"] * hy * sy - hy * zoom
        step = 2.0 * zoom / (width - 1)
        yield np.asarray([x0, y0, hx * step, hy * step], np.float32)


def reference(view, n: int, width: int, threshold: int) -> np.ndarray:
    """Escape counts of iterations [0, n) in float32, all pixels in lockstep.

    Only pixels still alive are updated, so the cost follows the frame's
    escape iterations and not ``n * threshold``.
    """
    x0, y0, dx, dy = np.asarray(view, np.float32)
    idx = np.arange(n, dtype=np.int32)
    cr = x0 + (idx % width).astype(np.float32) * dx
    ci = y0 + (idx // width).astype(np.float32) * dy
    counts = np.zeros(n, np.int32)
    alive = np.arange(n)
    zr = np.zeros(n, np.float32)
    zi = np.zeros(n, np.float32)
    four = np.float32(4.0)
    for _ in range(threshold):
        zr, zi = _step(zr, zi, cr, ci)
        keep = zr * zr + zi * zi < four
        alive, zr, zi, cr, ci = alive[keep], zr[keep], zi[keep], cr[keep], ci[keep]
        counts[alive] += 1
        if not alive.size:
            break
    return counts
