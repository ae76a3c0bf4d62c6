"""Pipeline parallelism: GPipe-style microbatch pipelining under shard_map.

For deployments where a layer-stack does not fit even 2D-sharded (or where the
mesh offers a spare axis), the layer dimension of the stacked parameters is
sharded over a "pipe" mesh axis; microbatches stream through the stages with
``ppermute`` handoffs.  The fill/drain schedule is the classic GPipe one:
at tick t, stage s processes microbatch (t - s); M microbatches across S
stages finish in M + S - 1 ticks (bubble fraction (S-1)/(M+S-1)).

This is an optional feature (the assigned meshes use data x model); it is
exercised by tests/test_pipeline.py on a placeholder-device mesh.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe_forward", "bubble_fraction"]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_forward(
    block_fn: Callable,  # (params_slice, h) -> h
    stacked_params,  # pytree, leaves [L, ...] with L % n_stages == 0
    micro_inputs: jnp.ndarray,  # [M, B_m, ...] microbatch stack
    mesh: Mesh,
    axis: str = "pipe",
) -> jnp.ndarray:
    """Returns [M, B_m, ...] outputs after all L layers, pipelined over the
    ``axis`` mesh dimension."""

    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = micro_inputs.shape[0]

    def stage_fn(params_local, micro_in):
        s_idx = jax.lax.axis_index(axis)
        s_total = jax.lax.axis_size(axis)

        def apply_local(h):
            def body(c, pl):
                return block_fn(pl, c), None

            out, _ = jax.lax.scan(body, h, params_local)
            return out

        perm = [(i, (i + 1) % s_total) for i in range(s_total)]

        def tick(carry, t):
            buf, outs = carry
            mb = jax.lax.dynamic_index_in_dim(
                micro_in, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            h_in = jnp.where(s_idx == 0, mb, buf)
            h_out = apply_local(h_in)
            buf_next = jax.lax.ppermute(h_out, axis, perm)
            rec = t - (s_total - 1)
            is_last = s_idx == s_total - 1
            do_rec = is_last & (rec >= 0) & (rec < m)
            outs = jnp.where(
                do_rec,
                jax.lax.dynamic_update_index_in_dim(
                    outs, h_out, jnp.clip(rec, 0, m - 1), 0
                ),
                outs,
            )
            return (buf_next, outs), None

        outs0 = jnp.zeros_like(micro_in)
        buf0 = jnp.zeros_like(micro_in[0])
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(m + s_total - 1))
        # results live on the last stage; replicate them
        return jax.lax.psum(jnp.where(s_idx == s_total - 1, outs, 0.0), axis)

    param_specs = jax.tree.map(
        lambda x: P(axis, *([None] * (x.ndim - 1))), stacked_params
    )
    return jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, micro_inputs)
