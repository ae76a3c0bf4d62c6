"""Pallas TPU kernel: chunked Mamba-1 selective scan.

The recurrence  h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t,
               y_t = <h_t, C_t> + D * x_t
is inherently sequential in t, but on TPU we (a) tile the channel dimension
(block_d) so each grid cell's state (block_d x N) sits in VMEM scratch and the
per-step elementwise work fills the VPU, and (b) chunk the sequence into
block_l slabs carried by a sequential innermost grid axis — HBM traffic is
one read of each (x, dt, B, C) slab and one write of y, with the state never
leaving VMEM.  This is the TPU-idiomatic shape of the paper-adjacent "chunked
iteration space" pattern (DESIGN.md Sec. 5): the chunk schedule here is fixed
(block_l), chosen for VMEM residency rather than load balance.

dt is expected pre-softplus'd; A is the raw (negative) continuous-time matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref,  # blocks, see specs below
    y_ref,  # (1, block_l, block_d)
    h_scr,  # VMEM (block_d, N) f32 — the SSM state
    *,
    block_l: int,
):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)  # (block_d, N)
    dskip = dskip_ref[0].astype(jnp.float32)  # (block_d,)

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)  # (block_d,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)  # (block_d,)
        b_t = b_ref[0, t, :].astype(jnp.float32)  # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)  # (N,)
        da = jnp.exp(dt_t[:, None] * a)  # (block_d, N)
        dbx = (dt_t * x_t)[:, None] * b_t[None, :]  # (block_d, N)
        h = da * h + dbx
        y_t = jnp.sum(h * c_t[None, :], axis=1) + dskip * x_t  # (block_d,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_l, step, h_scr[...])


def mamba_scan_pallas(
    x: jnp.ndarray,  # [B, L, D]
    dt: jnp.ndarray,  # [B, L, D] (post-softplus)
    a: jnp.ndarray,  # [D, N]
    b: jnp.ndarray,  # [B, L, N]
    c: jnp.ndarray,  # [B, L, N]
    d_skip: jnp.ndarray,  # [D]
    *,
    block_l: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    bsz, l, d = x.shape
    n = a.shape[1]
    assert l % block_l == 0 and d % block_d == 0, (l, d, block_l, block_d)
    num_l = l // block_l
    num_d = d // block_d

    kernel = functools.partial(_mamba_kernel, block_l=block_l)
    return pl.pallas_call(
        kernel,
        grid=(bsz, num_d, num_l),  # innermost sequential over sequence chunks
        in_specs=[
            pl.BlockSpec((1, block_l, block_d), lambda b_, di, li: (b_, li, di)),
            pl.BlockSpec((1, block_l, block_d), lambda b_, di, li: (b_, li, di)),
            pl.BlockSpec((block_d, n), lambda b_, di, li: (di, 0)),
            pl.BlockSpec((1, block_l, n), lambda b_, di, li: (b_, li, 0)),
            pl.BlockSpec((1, block_l, n), lambda b_, di, li: (b_, li, 0)),
            pl.BlockSpec((1, block_d), lambda b_, di, li: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, block_l, block_d), lambda b_, di, li: (b_, li, di)),
        out_shape=jax.ShapeDtypeStruct((bsz, l, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba_selective_scan",
    )(x, dt, a, b, c, d_skip.reshape(1, -1))
