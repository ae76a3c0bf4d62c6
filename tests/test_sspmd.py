"""Device-level (shard_map) DCA self-scheduler tests.

Runs on however many devices the test process sees (1 on CPU, or more under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in dedicated CI jobs);
the multi-device semantics are additionally emulated here by vmapping the
per-device computation over the axis via shard_map on a 1..n-device mesh.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.schedule import build_schedule_dca
from repro.core.sspmd import dca_schedule_scan, num_rounds_upper_bound
from repro.core.techniques import DLSParams


def _device_mesh():
    devs = np.array(jax.devices())
    return Mesh(devs, ("pe",))


@pytest.mark.parametrize("tech", ["gss", "fac", "tss", "fiss", "static", "ss"])
def test_dca_schedule_scan_covers_loop(tech):
    n_dev = len(jax.devices())
    params = DLSParams(N=2048, P=n_dev)
    mesh = _device_mesh()

    @jax.jit
    def run():
        def inner():
            offs, sizes = dca_schedule_scan(tech, params, "pe")
            return offs[None], sizes[None]

        return jax.shard_map(
            inner, mesh=mesh, in_specs=(), out_specs=(P("pe"), P("pe")),
            check_vma=False,
        )()

    offs, sizes = run()
    offs = np.asarray(offs).reshape(-1)  # [n_dev * rounds]
    sizes = np.asarray(sizes).reshape(-1)
    # collect claimed ranges across devices and rounds
    claimed = [(o, o + s) for o, s in zip(offs, sizes) if s > 0]
    claimed.sort()
    # complete, non-overlapping coverage of [0, N)
    cursor = 0
    for lo, hi in claimed:
        assert lo == cursor, f"gap/overlap at {lo} (expected {cursor})"
        cursor = hi
    assert cursor == params.N


@pytest.mark.parametrize("tech", ["gss", "fac"])
def test_dca_scan_matches_host_schedule(tech):
    """Device rounds must claim exactly the host-side DCA schedule's chunks."""
    n_dev = len(jax.devices())
    params = DLSParams(N=1000, P=n_dev)
    mesh = _device_mesh()

    @jax.jit
    def run():
        def inner():
            offs, sizes = dca_schedule_scan(tech, params, "pe")
            return offs[None], sizes[None]

        return jax.shard_map(inner, mesh=mesh, in_specs=(), out_specs=(P("pe"), P("pe")),
                             check_vma=False)()

    offs, sizes = run()
    dev_pairs = sorted(
        (int(o), int(s))
        for o, s in zip(np.ravel(offs), np.ravel(sizes))
        if s > 0
    )
    host = build_schedule_dca(tech, params)
    host_pairs = sorted(zip(host.offsets.tolist(), host.sizes.tolist()))
    # f32 vs f64 ceil boundaries can shift a chunk by 1 near the tail; require
    # head exactness and total-coverage equality
    assert dev_pairs[0] == host_pairs[0]
    assert sum(s for _, s in dev_pairs) == sum(s for _, s in host_pairs) == params.N
    exact = sum(1 for a, b in zip(dev_pairs, host_pairs) if a == b)
    assert exact >= int(0.9 * len(host_pairs))


def test_rounds_upper_bound():
    params = DLSParams(N=1000, P=7)
    assert num_rounds_upper_bound(params) * 7 >= 1000
