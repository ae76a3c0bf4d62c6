"""Device-level DCA self-scheduling under SPMD (shard_map) — the TPU adaptation.

The paper's runtime is asynchronous: PEs fetch-and-add a shared counter the
moment they go idle.  A TPU pod running a jitted program is bulk-synchronous,
so we adapt DCA to *scheduling rounds*: in round r, the P devices of a mesh
axis claim steps  i = r*P + axis_index  simultaneously.  Because every chunk
size is a pure function of its step index (the paper's "straightforward
formula" requirement), each device computes BOTH its chunk size and its chunk
offset locally — the round state (step counter, queue head) advances by a
*replicated deterministic* update with **zero communication**.  The serialized
MPI fetch-and-add becomes: nothing at all.  This is strictly stronger than the
MPI implementation and is only possible because of the paper's contribution.

The CCA baseline is also implemented for comparison: device 0 computes the P
chunk sizes of the round with the *recursive* formula (a lax.scan — inherently
sequential) and the result is broadcast from device 0 (psum of a masked
value), reproducing the master bottleneck structurally (the scan's sequential
HLO + one collective per round).

``dca_round_assignments`` is the building block used by
runtime/straggler.py (microbatch self-scheduling) and data/scheduler.py.

Each round form opens its ``jax.named_scope`` (``tracing.SCOPES``), so its
ops carry a stable name in their metadata for a device trace to attribute.
A scope sets op metadata only; it adds no op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .techniques_jnp import (
    TECH_IDS,
    default_head_cap,
    pack_params,
    prefix_for_steps,
    sizes_for_steps,
)

__all__ = [
    "dca_round_assignments",
    "dca_round_assignments_stateless",
    "dca_schedule_scan",
    "dca_schedule_stateless",
    "dca_schedule_for_spec",
    "cca_round_assignments",
    "cca_schedule_scan",
    "num_rounds_upper_bound",
]


def dca_round_assignments(round_state, tech_id, pv, axis_name: str):
    """One DCA scheduling round inside shard_map.

    round_state: (i0, lp0) — replicated int32 scalars: next step index and
        queue head.  Pure function of the round number, so identical on every
        device by construction (no sync needed to maintain it).
    Returns: ((new_i0, new_lp0), (my_offset, my_size)) — this device's chunk;
        size 0 <=> queue exhausted (device idles / masks its work).
    """
    i0, lp0 = round_state
    n_dev = jax.lax.axis_size(axis_name)
    with jax.named_scope("dca_round"):
        j = jax.lax.axis_index(axis_name)

        # Chunk calculation (distributed, the paper's Sec. 4): every device
        # evaluates the closed form for all P steps of this round — O(P)
        # flops, fully replicated, zero bytes on the wire.
        steps = i0.astype(jnp.float32) + jnp.arange(n_dev, dtype=jnp.float32)
        raw = jnp.maximum(jnp.round(sizes_for_steps(tech_id, steps, pv)), 1.0).astype(jnp.int32)

        # Chunk assignment (the fetch-and-add): exclusive prefix sum over the
        # round's sizes, clamped to the remaining iterations.
        n_total = pv[0].astype(jnp.int32)
        excl = jnp.cumsum(raw) - raw  # [P]
        starts = lp0 + excl
        sizes = jnp.clip(n_total - starts, 0, raw)

        my_offset = starts[j]
        my_size = sizes[j]
        new_state = (i0 + n_dev, jnp.minimum(lp0 + jnp.sum(raw), n_total))
    return new_state, (my_offset, my_size)


def dca_round_assignments_stateless(round_idx, tech_id, pv, axis_name: str,
                                    head_cap: int = 4096):
    """One DCA scheduling round with ZERO carried state.

    ``dca_round_assignments`` already needs no communication, but it still
    threads (i0, lp0) through a scan.  Here both are derived from the round
    number alone via the closed-form prefix (DESIGN.md Sec. 7): device j's
    step is ``round_idx*P + j`` and its offset is ``prefix(step)`` — a pure
    function, so rounds can be evaluated out of order, re-entered after
    preemption, or vmapped in bulk with no carried dependency at all.

    Returns (my_offset, my_size); size 0 <=> queue exhausted.

    ``head_cap`` must come from ``default_head_cap`` sized to the *largest
    step index this device will evaluate* (rounds * axis size + axis size) —
    an undersized cap silently mis-prices gss/tap/pls/rnd offsets past it.
    ``dca_schedule_stateless`` derives it correctly; pass-through callers
    must do the same.
    """
    n_dev = jax.lax.axis_size(axis_name)
    with jax.named_scope("dca_round"):
        j = jax.lax.axis_index(axis_name)
        n_total = pv[0]
        step = (jnp.asarray(round_idx, jnp.int32) * n_dev + j).astype(jnp.float32)
        raw = jnp.clip(jnp.round(sizes_for_steps(tech_id, step, pv)), 1.0, n_total)
        base = prefix_for_steps(tech_id, step, pv, head_cap=head_cap)
        my_offset = jnp.clip(base, 0.0, n_total).astype(jnp.int32)
        my_size = jnp.clip(n_total - base, 0.0, raw).astype(jnp.int32)
    return my_offset, my_size


def dca_schedule_stateless(tech_name: str, params, axis_name: str,
                           max_rounds: int = None):
    """Full per-device schedule from the closed-form prefix — no scan at all.

    The stateful ``dca_schedule_scan`` walks rounds sequentially because the
    queue head is carried; with the closed-form prefix every round is
    independent, so the whole schedule is one vectorized evaluation (the
    HLO contains no sequential chain — compare the scan in the CCA baseline).
    """
    tech_id = TECH_IDS[tech_name]
    pv = pack_params(params)
    if max_rounds is None:
        max_rounds = num_rounds_upper_bound(params)

    n_dev = jax.lax.axis_size(axis_name)  # a python int inside shard_map
    # size the prefix head to the largest step index actually evaluated —
    # steps stride by the mesh axis size, which may exceed params.P
    head_cap = default_head_cap(tech_name, params, max_rounds * n_dev + n_dev)
    with jax.named_scope("dca_schedule"):
        j = jax.lax.axis_index(axis_name)
        n_total = pv[0]
        steps = (jnp.arange(max_rounds, dtype=jnp.int32) * n_dev + j).astype(jnp.float32)
        raw = jnp.clip(jnp.round(sizes_for_steps(tech_id, steps, pv)), 1.0, n_total)
        base = prefix_for_steps(tech_id, steps, pv, head_cap=head_cap)
        offs = jnp.clip(base, 0.0, n_total).astype(jnp.int32)
        sizes = jnp.clip(n_total - base, 0.0, raw).astype(jnp.int32)
    return offs, sizes


def dca_schedule_for_spec(spec, axis_name: str, max_rounds: int = None):
    """``ScheduleSpec`` front-end for the device-level scheduler — the SPMD
    face of the unified ChunkSource API (see core/source.py).

    The BSP adaptation cannot hold a Python source object inside a compiled
    program; what it *can* share is the spec: the same (technique, N, P,
    mode) that builds a host ``ChunkSource`` here builds the per-device
    stateless schedule.  Feedback techniques have no closed form, so specs
    resolving to ``adaptive`` are rejected with the same message a
    ``StaticSource`` build would produce.
    """
    eff = spec.effective_mode
    if eff != "dca":
        raise ValueError(
            f"device-level scheduling requires closed forms (dca); spec "
            f"resolves to {eff!r} — adaptive/cca sources are host-only"
        )
    return dca_schedule_stateless(
        spec.technique, spec.to_params(), axis_name, max_rounds=max_rounds
    )


def cca_round_assignments(round_state, tech_name: str, params, axis_name: str):
    """CCA baseline round: device 0 walks the recursion, result broadcast.

    The recursion is expressed as a lax.scan over the P steps of the round
    (sequential chain in the HLO — the master's serialization, visible to the
    compiler) followed by a psum broadcast from device 0 (the master->worker
    message).  Supports gss/tss/fac/fiss recursions; used for benchmarks
    contrasting the two execution models on-device.
    """
    i0, lp0, prev, remaining = round_state
    n_dev = jax.lax.axis_size(axis_name)
    p_f = jnp.float32(params.P)

    def step(carry, idx):
        i, prev_k, rem = carry
        if tech_name == "gss":
            k = jnp.ceil(rem / p_f)
        elif tech_name == "tss":
            k0 = jnp.ceil(params.N / (2.0 * p_f))
            s = jnp.ceil(2.0 * params.N / (k0 + 1.0))
            c = jnp.floor((k0 - 1.0) / jnp.maximum(s - 1.0, 1.0))
            k = jnp.where(i == 0, k0, prev_k - c)
        elif tech_name == "fac":
            k_new = jnp.ceil(rem / (2.0 * p_f))
            k = jnp.where(jnp.mod(i, params.P) == 0, k_new, prev_k)
        elif tech_name == "fiss":
            b = float(params.fiss_b)
            k0 = jnp.floor(params.N / ((2.0 + b) * p_f))
            c = jnp.floor(2.0 * params.N * (1.0 - b / (2.0 + b)) / (p_f * b * max(b - 1.0, 1.0)))
            k = jnp.where(i == 0, k0, jnp.where(jnp.mod(i, params.P) == 0, prev_k + c, prev_k))
        else:
            raise ValueError(f"cca on-device recursion not implemented for {tech_name}")
        k = jnp.maximum(k, 1.0)
        k_clamped = jnp.minimum(k, rem)
        return (i + 1, k, rem - k_clamped), k_clamped

    # Master-only compute: mask the scan's *result* by device id and broadcast
    # with a psum — workers idle while the master walks the chain.
    with jax.named_scope("cca_round"):
        j = jax.lax.axis_index(axis_name)
        (i_end, prev_end, rem_end), ks = jax.lax.scan(
            step, (i0.astype(jnp.float32), prev, remaining), jnp.arange(n_dev)
        )
        is_master = (j == 0).astype(jnp.float32)
        with jax.named_scope("cca_bcast"):
            ks = jax.lax.psum(ks * is_master, axis_name)  # broadcast master's chunks
            rem_end = jax.lax.psum(rem_end * is_master, axis_name)
            prev_end = jax.lax.psum(prev_end * is_master, axis_name)

        ks_i = ks.astype(jnp.int32)
        excl = jnp.cumsum(ks_i) - ks_i
        my_offset = lp0 + excl[j]
        my_size = ks_i[j]
        new_state = (i0 + n_dev, lp0 + jnp.sum(ks_i), prev_end, rem_end)
    return new_state, (my_offset, my_size)


def num_rounds_upper_bound(params) -> int:
    """Rounds needed to drain N iterations with P devices at >=1 iter/chunk."""
    import math

    return math.ceil(params.N / max(params.min_chunk, 1) / params.P)


def dca_schedule_scan(tech_name: str, params, axis_name: str, max_rounds: int = None):
    """Full per-device schedule via lax.scan over DCA rounds (inside shard_map).

    Returns (offsets[r], sizes[r]) for this device across rounds — used to
    drive masked work loops (e.g. microbatch accumulation with self-scheduled
    microbatches).  Communication-free by construction.
    """
    tech_id = TECH_IDS[tech_name]
    pv = pack_params(params)
    if max_rounds is None:
        max_rounds = num_rounds_upper_bound(params)

    def body(state, _):
        state, (off, size) = dca_round_assignments(state, tech_id, pv, axis_name)
        return state, (off, size)

    init = (jnp.int32(0), jnp.int32(0))
    _, (offs, sizes) = jax.lax.scan(body, init, None, length=max_rounds)
    return offs, sizes


def cca_schedule_scan(tech_name: str, params, axis_name: str, max_rounds: int = None):
    """Full per-device CCA baseline schedule: ``cca_round_assignments`` rounds
    under a lax.scan (inside shard_map), the counterpart of
    ``dca_schedule_scan``.  Returns (offsets[r], sizes[r]) for this device."""
    if max_rounds is None:
        max_rounds = num_rounds_upper_bound(params)

    def body(state, _):
        return cca_round_assignments(state, tech_name, params, axis_name)

    init = (jnp.int32(0), jnp.int32(0), jnp.float32(0.0), jnp.float32(params.N))
    _, (offs, sizes) = jax.lax.scan(body, init, None, length=max_rounds)
    return offs, sizes
