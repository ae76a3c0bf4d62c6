"""launch.rules: divisibility-driven sharding decisions hold for every
(arch x shape x mesh) — validated structurally without compiling."""

import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


PROG = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"  # skip TPU probing in the bare env
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import math
    import jax
    from repro.configs import ARCH_NAMES, SHAPES, get_config, supported_shapes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.rules import build_rules, plan_for, mesh_axes

    for multi_pod in (False, True):
        mesh = make_production_mesh(multi_pod=multi_pod)
        ax = mesh_axes(mesh)
        for arch in ARCH_NAMES:
            cfg = get_config(arch)
            for shape_name in supported_shapes(cfg):
                shape = SHAPES[shape_name]
                rules = build_rules(cfg, mesh, shape)
                plan = plan_for(cfg, shape, mesh)
                r = rules.rules
                model = ax["model"]

                def ok(n, axis):
                    if axis is None: return True
                    sz = math.prod(ax[a] for a in (axis if isinstance(axis, tuple) else (axis,)))
                    return n % sz == 0

                assert ok(cfg.vocab, r["vocab"]), (arch, "vocab")
                assert ok(cfg.n_heads or 1, r["heads"]), (arch, "heads")
                assert ok(cfg.n_kv_heads or 1, r["kv_heads"]), (arch, "kv")
                assert ok(cfg.d_ff or 1, r["mlp"]), (arch, "mlp")
                assert ok(cfg.d_model, r["embed"]), (arch, "embed/fsdp")
                if cfg.n_experts:
                    assert ok(cfg.n_experts, r["experts"]), (arch, "experts")
                if shape.kind == "train":
                    assert shape.global_batch % plan.n_microbatches == 0
                # batch sharding must divide when set
                if r["batch"] is not None:
                    assert ok(shape.global_batch, r["batch"]), (arch, shape_name, "batch")
    print("RULES_OK")
""")


def test_rules_valid_for_all_cells():
    res = subprocess.run(
        [sys.executable, "-c", PROG], capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}, cwd=REPO_ROOT,
    )
    assert "RULES_OK" in res.stdout, f"stdout={res.stdout}\nstderr={res.stderr[-2500:]}"


COMPRESS_PROG = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"  # skip TPU probing in the bare env
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import ef_topk_allreduce

    mesh = jax.make_mesh((4,), ("dp",))
    g = jax.random.normal(jax.random.key(0), (4, 256))  # per-device rows
    e = jnp.zeros((4, 256))

    def f(g, e):
        return ef_topk_allreduce(g, e, "dp", ratio=0.25)

    out, err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                                     out_specs=(P("dp"), P("dp"))))(g, e)
    # index host copies: the mesh's axes are Explicit, so the sharded
    # results cannot be indexed as plain device arrays
    g, out, err = np.asarray(g), np.asarray(out), np.asarray(err)
    # every device's reduced gradient equals the mean of the compressed locals
    comp = []
    for i in range(4):
        gi = g[i]
        k = int(256 * 0.25)
        thr = np.sort(np.abs(gi))[-k]
        comp.append(np.where(np.abs(gi) >= thr, gi, 0.0))
    expected = np.mean(comp, axis=0)
    for i in range(4):
        np.testing.assert_allclose(out[i], expected, atol=1e-5)
    # error feedback holds the residual
    np.testing.assert_allclose(err[0], g[0] - comp[0], atol=1e-5)
    print("COMPRESS_OK")
""")


def test_ef_allreduce_in_shard_map_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", COMPRESS_PROG], capture_output=True, text=True,
        timeout=300, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}, cwd=REPO_ROOT,
    )
    assert "COMPRESS_OK" in res.stdout, f"stdout={res.stdout}\nstderr={res.stderr[-2500:]}"
