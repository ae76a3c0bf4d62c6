"""Closed-form prefix contract (DESIGN.md Sec. 7), host and device layers.

For each technique the prefix must equal the explicit cumulative sum of the
clamped closed-form sizes wherever that sum is < N, and be >= N beyond the
drain point (where chunk assignment clamps to the remaining work anyway).
"""

import numpy as np
import pytest

from repro.core.schedule import build_schedule_dca, chunk_of_step, drain_steps
from repro.core.techniques import (
    DLSParams,
    TECHNIQUES,
    closed_form_prefix,
)
from repro.core.techniques_jnp import (
    TECH_IDS,
    default_head_cap,
    pack_params,
    prefix_for_steps,
    sizes_for_steps,
)

DCA_TECHS = sorted(n for n, t in TECHNIQUES.items() if t.dca_supported)

SHAPES = [(1000, 4), (262_144, 256), (777, 13), (54_321, 37), (12, 5), (1, 1),
          (2_000_000, 256)]


def _explicit_prefix(tech, imax, p):
    mce = float(max(p.min_chunk, 1))
    js = np.arange(imax, dtype=np.int64)
    sizes = np.clip(np.round(TECHNIQUES[tech].closed_form(js, p)), mce, float(p.N))
    return np.concatenate([[0.0], np.cumsum(sizes)])


@pytest.mark.parametrize("n,p", SHAPES)
@pytest.mark.parametrize("tech", DCA_TECHS)
def test_host_prefix_matches_cumsum(tech, n, p):
    params = DLSParams(N=n, P=p)
    imax = min(n + 2 * p + 5, 4000)
    idx = np.arange(imax + 1, dtype=np.int64)
    exp = _explicit_prefix(tech, imax, params)[idx]
    got = closed_form_prefix(tech, idx, params)
    ok = np.where(exp < n, got == exp, got >= n)
    assert ok.all(), f"{tech} N={n} P={p}: first bad i={np.argmin(ok)}"


@pytest.mark.parametrize("tech", DCA_TECHS)
def test_host_prefix_far_indices(tech):
    """Prefix stays correct (and monotone) at indices far past the drain."""
    params = DLSParams(N=50_000, P=64)
    idx = np.asarray([0, 1, 10_000, 49_999, 50_000, 123_456, 10 ** 7])
    got = closed_form_prefix(tech, idx, params)
    assert (np.diff(got) >= 0).all()
    assert got[0] == 0.0
    assert (got[3:] >= params.N - 0).all() or got[3] < params.N  # drained tail >= N
    assert got[-1] >= params.N


@pytest.mark.parametrize("n,p", [(1000, 4), (65_536, 64), (54_321, 37)])
@pytest.mark.parametrize("tech", DCA_TECHS)
def test_jnp_prefix_consistent_with_jnp_sizes(tech, n, p):
    """Device prefix must equal the f32 cumsum of the device's own clamped
    sizes (internal consistency is what the parallel Pallas grid relies on)."""
    import jax.numpy as jnp

    params = DLSParams(N=n, P=p)
    pv = pack_params(params)
    max_steps = min(n, 3000)
    js = jnp.arange(max_steps, dtype=jnp.float32)
    tid = TECH_IDS[tech]
    sz = np.asarray(jnp.clip(jnp.round(sizes_for_steps(tid, js, pv)), 1.0, float(n)))
    exp = np.concatenate([[0.0], np.cumsum(sz.astype(np.float64))])
    hc = default_head_cap(tech, params, max_steps + 1)
    idx = np.arange(max_steps + 1)
    got = np.asarray(
        prefix_for_steps(tid, jnp.asarray(idx, jnp.float32), pv, head_cap=hc),
        dtype=np.float64,
    )
    ok = np.where(exp < n, got == exp, got >= n)
    assert ok.all(), f"{tech} N={n} P={p}: first bad i={np.argmin(ok)}"


@pytest.mark.parametrize("p,exact_below", [(2, 127), (4, 16), (37, 1), (256, 4), (1024, 3)])
def test_pow_int_exact_powers_and_bounded_error(p, exact_below):
    """gss/tap/pls form ((p-1)/p)**k from additions and products, so the
    result is exact while every power it forms is an f32, and within 128 ulp
    of the float64 power of the same f32 base up to k = 8191."""
    import jax.numpy as jnp

    from repro.core.techniques_jnp import _pow_int

    base = np.float32((p - 1) / p)
    k = np.arange(8192, dtype=np.float32)
    got = np.asarray(_pow_int(base, jnp.asarray(k))).astype(np.float64)
    want = np.float64(base) ** k.astype(np.float64)
    np.testing.assert_array_equal(got[:exact_below], want[:exact_below])
    normal = want > 1e-30
    np.testing.assert_allclose(got[normal], want[normal], rtol=128 * 2.0 ** -24)


@pytest.mark.parametrize("tech", DCA_TECHS)
def test_chunk_of_step_prefix_path(tech):
    """O(1) per-PE chunk lookup (closed-form prefix) matches the schedule."""
    params = DLSParams(N=10_000, P=16)
    sched = build_schedule_dca(tech, params)
    for i in [0, 1, sched.num_steps // 2, sched.num_steps - 1]:
        off, size = chunk_of_step(tech, i, params)
        assert off == sched.offsets[i], (tech, i)
        assert size == sched.sizes[i], (tech, i)


@pytest.mark.parametrize("tech", DCA_TECHS)
def test_drain_steps_bounds_schedule(tech):
    params = DLSParams(N=20_000, P=32)
    sched = build_schedule_dca(tech, params)
    assert drain_steps(tech, params) == sched.num_steps


def test_stateless_sspmd_matches_scan():
    """The state-free round assignment (round state derived from the round
    number alone) claims exactly the chunks of the carried-state scan."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.sspmd import dca_schedule_scan, dca_schedule_stateless

    mesh = Mesh(np.array(jax.devices()), ("pe",))
    n_dev = len(jax.devices())
    params = DLSParams(N=2048, P=n_dev)
    for tech in DCA_TECHS:
        def scan_fn():
            offs, sizes = dca_schedule_scan(tech, params, "pe")
            return offs[None], sizes[None]

        def stateless_fn():
            offs, sizes = dca_schedule_stateless(tech, params, "pe")
            return offs[None], sizes[None]

        o1, s1 = (np.ravel(x) for x in jax.jit(jax.shard_map(
            scan_fn, mesh=mesh, in_specs=(), out_specs=(P("pe"), P("pe")),
            check_vma=False))())
        o2, s2 = (np.ravel(x) for x in jax.jit(jax.shard_map(
            stateless_fn, mesh=mesh, in_specs=(), out_specs=(P("pe"), P("pe")),
            check_vma=False))())
        np.testing.assert_array_equal(s1, s2, err_msg=tech)
        keep = s1 > 0
        np.testing.assert_array_equal(o1[keep], o2[keep], err_msg=tech)
        assert s2.sum() == params.N, tech
