"""SPMD path: ``sspmd.dca_schedule_for_spec`` under ``jax.shard_map``.

A frame is one jitted program over a mesh of the cell's chips.  Each chip
computes its own (offset, size) for every round from the spec, with no
communication, and runs the device body on its chunk of each round in a
``fori_loop``.  The frame ends when every chip's results are ready.
"""

from __future__ import annotations

import contextlib
import math

import numpy as np

SPANS = ("dispatch", "block")


class Frame:
    def __init__(self, view, out, offs, sizes):
        self.view, self._out, self._offs, self._sizes = view, out, offs, sizes
        self.complete = True

    def _host(self):
        return (np.asarray(self._out), np.asarray(self._offs), np.asarray(self._sizes))

    def chunks(self):
        """(step, lo, size) of every live round, step = round * chips + chip."""
        _, offs, sizes = self._host()
        chips = offs.shape[0]
        return [(r * chips + j, int(offs[j, r]), int(sizes[j, r]))
                for j, r in zip(*np.nonzero(sizes))]

    def tiles(self):
        out, offs, sizes = self._host()
        return [(int(offs[j, r]), int(sizes[j, r]), out[j, r].reshape(-1))
                for j, r in zip(*np.nonzero(sizes))]


class Runner:
    spans = SPANS

    def __init__(self, cfg, traffic, devices, tile, tile_size, schedule):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec

        from repro.core.source import ScheduleSpec
        from repro.core.sspmd import dca_schedule_for_spec

        if traffic["delay_calc_s"]:
            raise ValueError("the SPMD path has no host claim to delay")
        if max(schedule) > tile_size:
            raise ValueError(f"a round runs one tile; chunks reach {max(schedule)} > {tile_size}")
        spec = ScheduleSpec(traffic["technique"], N=cfg["N"], P=cfg["P"], mode=traffic["mode"])
        chips = len(devices)
        rounds = math.ceil(len(schedule) / chips)  # as many as the loop's steps need
        mesh = jax.make_mesh((chips,), ("pe",), axis_types=(AxisType.Explicit,), devices=devices)
        shape = jax.eval_shape(tile, jax.ShapeDtypeStruct((2,), jnp.int32),
                               jax.ShapeDtypeStruct((4,), jnp.float32)).shape

        def per_chip(view):
            offs, sizes = dca_schedule_for_spec(spec, "pe", max_rounds=rounds)

            def body(r, out):
                res = tile(jnp.stack([offs[r], sizes[r]]), view)
                return jax.lax.dynamic_update_index_in_dim(out, res, r, 0)

            out = jax.lax.fori_loop(0, rounds, body, jnp.zeros((rounds, *shape), jnp.int32))
            return out[None], offs[None], sizes[None]

        pe = PartitionSpec("pe")
        self.step = jax.jit(jax.shard_map(per_chip, mesh=mesh, in_specs=PartitionSpec(),
                                          out_specs=(pe, pe, pe), check_vma=False))
        self.view_sharding = NamedSharding(mesh, PartitionSpec())

    def frame(self, view, traced: bool = False) -> Frame:
        import jax

        span = jax.profiler.TraceAnnotation if traced else (lambda _: contextlib.nullcontext())
        with span("dispatch"):
            out = self.step(jax.device_put(view, self.view_sharding))
        with span("block"):
            jax.block_until_ready(out)
        return Frame(view, *out)
