"""dls_chunks Pallas kernel: shape/technique sweeps vs the pure-jnp oracle
and the float64 host schedule builder."""

import numpy as np
import pytest

from repro.core.schedule import build_schedule_dca
from repro.core.techniques import DLSParams
from repro.core.techniques_jnp import TECH_IDS, pack_params
from repro.kernels.dls_chunks import dls_chunk_schedule, dls_chunk_schedule_ref

TECHS = ["static", "ss", "fsc", "gss", "tap", "tss", "fac", "tfss", "fiss", "viss", "rnd", "pls"]


@pytest.mark.parametrize("tech", TECHS)
@pytest.mark.parametrize("n,p", [(1000, 4), (262_144, 256), (40_000, 64)])
def test_kernel_matches_jnp_oracle(tech, n, p):
    """Kernel output must equal ref.py exactly (identical f32 math)."""
    params = DLSParams(N=n, P=p)
    sizes_k, offs_k = dls_chunk_schedule(tech, params)
    sizes_r, offs_r = dls_chunk_schedule_ref(TECH_IDS[tech], pack_params(params), len(sizes_k))
    np.testing.assert_array_equal(np.asarray(sizes_k), np.asarray(sizes_r))
    np.testing.assert_array_equal(np.asarray(offs_k), np.asarray(offs_r))


@pytest.mark.parametrize("tech", ["gss", "fac", "tss", "fiss"])
def test_kernel_matches_host_schedule_table2(tech):
    """At Table-2 scale the kernel reproduces the paper's chunk sequences."""
    params = DLSParams(N=1000, P=4)
    sizes_k, offs_k = dls_chunk_schedule(tech, params)
    keep = np.asarray(sizes_k) > 0
    host = build_schedule_dca(tech, params)
    np.testing.assert_array_equal(np.asarray(sizes_k)[keep], host.sizes)
    np.testing.assert_array_equal(np.asarray(offs_k)[keep], host.offsets)


@pytest.mark.parametrize("tech", TECHS)
def test_kernel_coverage_invariant(tech):
    """Non-overlapping complete coverage, straight from kernel output."""
    params = DLSParams(N=54_321, P=37)
    sizes, offs = dls_chunk_schedule(tech, params)
    sizes, offs = np.asarray(sizes), np.asarray(offs)
    keep = sizes > 0
    s, o = sizes[keep], offs[keep]
    assert o[0] == 0
    np.testing.assert_array_equal(o[1:], (o + s)[:-1])
    assert s.sum() == params.N


def test_kernel_multi_tile_offsets_continuous():
    """Schedules longer than one (8x128) tile: tile base offsets come from
    the closed-form prefix (no SMEM carry) and must still be continuous."""
    params = DLSParams(N=20_000, P=2)  # ss => 20k steps => 20 tiles
    sizes, offs = dls_chunk_schedule("ss", params)
    sizes, offs = np.asarray(sizes), np.asarray(offs)
    keep = sizes > 0
    assert keep.sum() == 20_000
    np.testing.assert_array_equal(offs[keep], np.arange(20_000))


@pytest.mark.parametrize("tech", ["gss", "fac", "fiss", "tss", "viss"])
def test_kernel_beyond_old_int32_bound(tech):
    """N > 1e6: the carry-saturation era capped the kernel at ~1e6 iterations
    (unclamped int32 tile prefix sums of increasing techniques overflowed).
    The stateless f32 tile offsets support N up to 2**23 — prove coverage at
    N = 2**22 for decreasing AND increasing techniques."""
    n = 4_194_304  # 2**22
    params = DLSParams(N=n, P=256)
    sizes, offs = dls_chunk_schedule(tech, params)
    sizes, offs = np.asarray(sizes), np.asarray(offs)
    keep = sizes > 0
    s, o = sizes[keep], offs[keep]
    assert s.sum() == n, f"{tech}: covered {s.sum()} of {n}"
    assert o[0] == 0
    np.testing.assert_array_equal(o[1:], (o + s)[:-1])
    # head of the schedule must agree with the float64 host builder
    host = build_schedule_dca(tech, params)
    head = min(64, len(host.sizes), len(s))
    np.testing.assert_array_equal(s[:head], host.sizes[:head])


def _rnd_u01_uint32_numpy(seed: int, i: np.ndarray) -> np.ndarray:
    """The rnd hash in uint32 arithmetic: the reference for its int32 form."""
    u = np.uint32
    x = i.astype(u) * u(0x9E3779B9) ^ (u(seed) * u(0x85EBCA6B) + u(0xC2B2AE35))
    x = (x ^ (x >> u(16))) * u(0x7FEB352D)
    x = (x ^ (x >> u(15))) * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x.astype(np.float32) / np.float32(4294967296.0)


@pytest.mark.parametrize("tech", ["rnd", "gss", "viss"])
def test_kernel_matches_jnp_oracle_at_2_pow_22(tech):
    """The kernel's roll-based within-tile prefix sum and the int32 rnd hash
    against the jnp oracle (``jnp.cumsum``) over a whole schedule, and the hash
    bit for bit against its uint32 form over every step index below 2**22."""
    from repro.core.techniques_jnp import _rnd_u01_u32

    params = DLSParams(N=2 ** 22, P=256)
    sizes_k, offs_k = dls_chunk_schedule(tech, params)
    sizes_r, offs_r = dls_chunk_schedule_ref(TECH_IDS[tech], pack_params(params), len(sizes_k))
    np.testing.assert_array_equal(np.asarray(sizes_k), np.asarray(sizes_r))
    np.testing.assert_array_equal(np.asarray(offs_k), np.asarray(offs_r))
    if tech == "rnd":
        i = np.arange(2 ** 22, dtype=np.int32)
        got = np.asarray(_rnd_u01_u32(np.int32(params.seed), i))
        np.testing.assert_array_equal(got, _rnd_u01_uint32_numpy(params.seed, i))


def test_kernel_schedule_under_jit_matches_eager():
    """``dls_chunk_schedule`` traces inside an outer jit (the parameters stay
    static), so its lowering can be inspected for the compiled kernel."""
    import jax

    params = DLSParams(N=54_321, P=37)
    jitted = jax.jit(lambda: dls_chunk_schedule("fac", params))()
    for got, want in zip(jitted, dls_chunk_schedule("fac", params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_rejects_n_beyond_f32_exact_range():
    with pytest.raises(ValueError):
        dls_chunk_schedule("gss", DLSParams(N=2 ** 23 + 1, P=256))
