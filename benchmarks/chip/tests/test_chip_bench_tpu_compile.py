"""The chip benchmark's device programs compile for a described v5e:2x2.

The tile kernel (float32 and the bfloat16 control) for one chip, and the
four-chip SPMD frame at the cell's own size, with no chip attached.  The
topology is described inside a fixture, so only the worker that runs this
file loads the TPU compiler."""

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _cell(config, traffic):
    return SimpleNamespace(
        config=json.loads((HERE / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((HERE / "traffic" / f"{traffic}.json").read_text()))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tile_kernel_compiles(topo, dtype):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from apps import mandelbrot

    one = SingleDeviceSharding(topo.devices[0])
    tile = mandelbrot.tile_fn(512, 512, getattr(jnp, dtype), interpret=False)
    compiled = tile.lower(jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one),
                          jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_spmd_frame_compiles(topo):
    import jax
    import jax.numpy as jnp

    import run
    from apps import mandelbrot

    cell = _cell("mandelbrot-t4-4chip", "fac-dca")
    tile = mandelbrot.tile_fn(512, 512, interpret=False)
    _, _, runner = run.build(cell, list(topo.devices), tile)
    compiled = runner.step.lower(
        jax.ShapeDtypeStruct((4,), jnp.float32, sharding=runner.view_sharding)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text  # DCA rounds: no exchange
