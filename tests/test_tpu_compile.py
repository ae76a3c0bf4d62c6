"""Compile rehearsal: the device path compiled for a described TPU v5e.

The TPU compiler installed with JAX compiles for a ``v5e:2x2`` topology that
is described, not attached.  This catches what interpret mode cannot: a
Pallas body Mosaic cannot lower, a layout it refuses, a program that does not
partition.  Nothing runs here, so a pass says nothing about results or time.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  JAX's persistent cache is off around these compiles, since an
entry compiled for a described chip cannot be read back without one.
"""

import math

import jax
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec, SingleDeviceSharding

from repro.core.schedule import build_schedule_cca, build_schedule_dca
from repro.core.sspmd import cca_schedule_scan, dca_schedule_scan, dca_schedule_stateless
from repro.core.techniques import DLSParams
from repro.core.techniques_jnp import TECH_NAMES_DCA
from repro.kernels.dls_chunks.kernel import dls_chunks_pallas
from repro.kernels.dls_chunks.ops import kernel_args

TABLE4 = (262_144, 256)
KERNEL_CASES = [(t, *TABLE4) for t in TECH_NAMES_DCA] + [("gss", 2 ** 23, 256),
                                                        ("ss", 2 ** 23, 256)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a CPU-only install has no TPU compiler; with one, failing to describe
    # the topology is a fault, not a skip
    pytest.importorskip("libtpu")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("pe",), axis_types=(AxisType.Explicit,))


@pytest.mark.parametrize("tech,n,p", KERNEL_CASES)
def test_dls_chunks_kernel_compiles_for_v5e(one_chip, tech, n, p):
    tech_id, pv, num_tiles, head_cap = kernel_args(tech, DLSParams(N=n, P=p))

    def schedule():
        return dls_chunks_pallas(tech_id, pv, num_tiles, head_cap=head_cap, interpret=False)

    compiled = jax.jit(schedule, out_shardings=(one_chip, one_chip)).lower().compile()
    assert "tpu_custom_call" in compiled.as_text()


ROUND_FORMS = {
    "dca_stateless": (dca_schedule_stateless, build_schedule_dca),
    "dca_scan": (dca_schedule_scan, build_schedule_dca),
    "cca_scan": (cca_schedule_scan, build_schedule_cca),
}


@pytest.mark.parametrize("form", sorted(ROUND_FORMS))
def test_sspmd_rounds_compile_for_four_v5e_chips(four_chips, form):
    """The rounds of ``chip_smoke.py --chips 4``: N=262,144 over P=4 chips.
    DCA needs no collective; the CCA baseline broadcasts the master's chunks."""
    schedule_fn, host_builder = ROUND_FORMS[form]
    params = DLSParams(N=TABLE4[0], P=4)
    rounds = math.ceil((host_builder("gss", params).num_steps + 64) / 4)

    def per_device():
        offs, sizes = schedule_fn("gss", params, "pe", max_rounds=rounds)
        return offs[None], sizes[None]

    spec = PartitionSpec("pe")
    step = jax.jit(jax.shard_map(per_device, mesh=four_chips, in_specs=(),
                                 out_specs=(spec, spec), check_vma=False))
    hlo = step.lower().compile().as_text()
    assert ("all-reduce" in hlo) == form.startswith("cca")
