"""Each SPMD round form opens its named scope, and a scope adds no exchange.

The forms are compiled inside ``shard_map`` over four CPU devices, which the
CPU backend gives only when told before it starts, so the compiles run in
one child process (``python test_sspmd_scopes.py``).  A scope lands in each
op's ``op_name`` metadata of the compiled HLO, which is what a device trace
names its ops by.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import tracing

N_DEV = 4

# form -> the scopes its ops carry; the DCA forms exchange nothing
FORMS = {
    "dca_schedule_stateless": {"dca_schedule"},
    "dca_schedule_for_spec": {"dca_schedule"},
    "dca_round_assignments": {"dca_round"},
    "dca_round_assignments_stateless": {"dca_round"},
    "dca_schedule_scan": {"dca_round"},
    "cca_round_assignments": {"cca_round", "cca_bcast"},
    "cca_schedule_scan": {"cca_round", "cca_bcast"},
}
DCA_FORMS = [f for f in FORMS if f.startswith("dca")]
CCA_FORMS = [f for f in FORMS if f.startswith("cca")]
COLLECTIVES = ("all-reduce", "all-gather")


def _compile_forms() -> dict:
    """Per form: the scopes in its compiled ops' metadata, and for each
    collective instruction its kind and ``op_name``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, PartitionSpec

    from repro.core import sspmd
    from repro.core.source import ScheduleSpec
    from repro.core.techniques import DLSParams
    from repro.core.techniques_jnp import TECH_IDS, pack_params

    params = DLSParams(N=4096, P=16)
    tech, pv, rounds = TECH_IDS["fac"], pack_params(params), 40
    calls = {
        "dca_schedule_stateless":
            lambda: sspmd.dca_schedule_stateless("fac", params, "pe", max_rounds=rounds),
        "dca_schedule_for_spec": lambda: sspmd.dca_schedule_for_spec(
            ScheduleSpec("fac", N=4096, P=16, mode="dca"), "pe", max_rounds=rounds),
        "dca_round_assignments": lambda: sspmd.dca_round_assignments(
            (jnp.int32(0), jnp.int32(0)), tech, pv, "pe")[1],
        "dca_round_assignments_stateless":
            lambda: sspmd.dca_round_assignments_stateless(jnp.int32(3), tech, pv, "pe"),
        "dca_schedule_scan":
            lambda: sspmd.dca_schedule_scan("fac", params, "pe", max_rounds=rounds),
        "cca_round_assignments": lambda: sspmd.cca_round_assignments(
            (jnp.int32(0), jnp.int32(0), jnp.float32(0.0), jnp.float32(4096.0)),
            "fac", params, "pe")[1],
        "cca_schedule_scan":
            lambda: sspmd.cca_schedule_scan("fac", params, "pe", max_rounds=rounds),
    }
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("pe",), axis_types=(AxisType.Explicit,))
    pe = PartitionSpec("pe")
    names = "|".join(tracing.SCOPES)
    out = {}
    for form, call in calls.items():
        def per_device(call=call):
            offs, sizes = call()
            return jnp.atleast_1d(offs)[None], jnp.atleast_1d(sizes)[None]

        step = jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(), out_specs=(pe, pe),
                                     check_vma=False))
        hlo = step.lower().compile().as_text()
        scopes = re.findall(rf'op_name="(?:[^"]*/)?({names})/', hlo)
        collectives = [
            (m[1], m[2]) for line in hlo.splitlines() if (m := re.search(
                rf" ({'|'.join(COLLECTIVES)})(?:-start)?\(.*op_name=\"([^\"]*)\"", line))]
        out[form] = {"scopes": sorted(set(scopes)), "collectives": collectives}
    return out


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N_DEV}")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = os.pathsep.join(p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, __file__], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_scopes_are_listed_once_beside_the_spans():
    assert len(set(tracing.SCOPES)) == len(tracing.SCOPES)
    assert not set(tracing.SCOPES) & set(tracing.SPANS)
    assert set(tracing.SCOPES) == set().union(*FORMS.values())


@pytest.mark.parametrize("form", sorted(FORMS))
def test_round_form_opens_its_scope(compiled, form):
    assert set(compiled[form]["scopes"]) == FORMS[form]


@pytest.mark.parametrize("form", DCA_FORMS)
def test_dca_form_compiles_with_no_collective(compiled, form):
    assert compiled[form]["collectives"] == []


@pytest.mark.parametrize("form", CCA_FORMS)
def test_cca_broadcast_is_the_only_exchange_and_lies_in_its_scope(compiled, form):
    found = compiled[form]["collectives"]
    assert found
    for kind, op_name in found:
        assert kind == "all-reduce" and "/cca_round/cca_bcast/" in op_name


if __name__ == "__main__":
    print(json.dumps(_compile_forms()))
