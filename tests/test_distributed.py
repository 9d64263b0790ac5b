"""Distribution tests that need >1 device: run in subprocesses with
``--xla_force_host_platform_device_count=8`` (tests themselves must see
the real 1-CPU world, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_with_devices(code: str, n_dev: int = 8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_mesh_shapes():
    out = run_with_devices("""
        import jax
        from repro.launch.mesh import make_production_mesh, make_host_mesh
        m = make_host_mesh(2)
        assert m.shape == {"data": 4, "model": 2}, m.shape
        print("ok", m.axis_names)
    """)
    assert "ok" in out


def test_small_dryrun_cell_on_8_devices():
    """End-to-end: lower+compile a tiny LM train step on a 4x2 mesh with
    the production sharding rules, assert collectives appear."""
    out = run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import registry
        from repro.launch.train import smoke_spec
        from repro.launch.mesh import make_host_mesh
        from repro.train.steps import build_bundle
        from repro.launch.analysis import collective_bytes
        spec = smoke_spec(registry.get_spec("granite-8b"))
        mesh = make_host_mesh(2)
        with mesh:
            b = build_bundle(spec, "train_4k", mesh)
            compiled = b.lower().compile()
        coll = collective_bytes(compiled.as_text())
        assert coll["total"] > 0, coll
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        print("ok", coll)
    """)
    assert "ok" in out


def test_real_sharded_train_step_runs():
    """Actually execute a sharded train step on 8 devices and check the
    loss decreases (data+model parallel numerics are right)."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.configs import registry
        from repro.launch.train import smoke_spec, init_state, make_batch_fn
        from repro.launch.mesh import make_host_mesh
        from repro.train.steps import build_bundle
        spec = smoke_spec(registry.get_spec("qwen2-moe-a2.7b"))
        mesh = make_host_mesh(2)
        with mesh:
            bundle = build_bundle(spec, "train_4k", mesh,
                                  overrides={"warmup": 1})
            step = bundle.jitted()
            state = init_state(spec, mesh, bundle)
            batch = make_batch_fn(spec, "train_4k")(0)
            losses = []
            for i in range(8):
                state, m = step(state, batch)
                losses.append(float(np.asarray(m["loss"])))
        assert losses[-1] < losses[0], losses
        print("ok", [round(x, 3) for x in losses])
    """)
    assert "ok" in out


def test_sharded_matches_single_device():
    """Same seed, same batch: 8-way sharded step == 1-device step."""
    code_tpl = """
        import jax, numpy as np
        from repro.configs import registry
        from repro.launch.train import smoke_spec, init_state, make_batch_fn
        from repro.launch.mesh import make_host_mesh
        from repro.train.steps import build_bundle
        spec = smoke_spec(registry.get_spec("granite-8b"))
        mesh = make_host_mesh({mp})
        with mesh:
            bundle = build_bundle(spec, "train_4k", mesh)
            step = bundle.jitted()
            state = init_state(spec, mesh, bundle)
            batch = make_batch_fn(spec, "train_4k")(0)
            state, m = step(state, batch)
        print("LOSS", float(np.asarray(m["loss"])))
    """
    l8 = run_with_devices(code_tpl.format(mp=2), n_dev=8)
    l1 = run_with_devices(code_tpl.format(mp=1), n_dev=1)
    v8 = float(l8.split("LOSS")[1])
    v1 = float(l1.split("LOSS")[1])
    assert abs(v8 - v1) < 5e-2, (v8, v1)


def test_compressed_crosspod_reduction():
    """int8 error-feedback cross-pod psum ≈ fp32 mean within quant error,
    and the error-feedback state absorbs the residual."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import (compressed_psum_pod,
                                                   init_error_feedback)
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        g_global = rng.standard_normal((2, 64)).astype(np.float32)

        def f(gs, es):
            return compressed_psum_pod({"g": gs}, {"g": es}, mesh)

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P(), P("pod")), check_vma=False)
        out, new_err = fn(jnp.asarray(g_global),
                          jnp.zeros_like(jnp.asarray(g_global)))
        want = g_global.mean(0)
        got = np.asarray(out["g"])[0]
        scale = np.abs(g_global).max() / 127
        assert np.abs(got - want).max() < scale, (got[:4], want[:4])
        # 4x fewer cross-pod bytes than fp32 ring allreduce at P=2
        print("ok maxerr", float(np.abs(got - want).max()))
    """)
    assert "ok" in out


def test_elastic_restore_across_meshes():
    """Elastic restart: checkpoint written under a (4,2) mesh restores
    onto a (2,4) mesh with resharded state and identical values."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        d = tempfile.mkdtemp()
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        state = {"w": jax.device_put(
                     jnp.arange(32.0).reshape(8, 4),
                     NamedSharding(mesh_a, P("data", "model"))),
                 "step": jnp.int32(7)}
        save_checkpoint(d, 7, state)
        # new topology: swap axis sizes
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        sh = {"w": NamedSharding(mesh_b, P("data", "model")),
              "step": NamedSharding(mesh_b, P())}
        got, step = restore_checkpoint(d, state, shardings=sh)
        assert step == 7
        assert got["w"].sharding.mesh.shape == {"data": 2, "model": 4}
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(32.0).reshape(8, 4))
        print("ok")
    """)
    assert "ok" in out


def test_islabel_query_sharded_matches_local():
    """The paper's query engine under the production sharding returns the
    same distances as the single-device engine."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ISLabelIndex, IndexConfig
        from repro.graphs import generators as gen
        n, src, dst, w = gen.er_graph(400, 3.0, seed=5)
        idx = ISLabelIndex.build(n, src, dst, w,
                                 IndexConfig(l_cap=128, label_chunk=128))
        r = np.random.default_rng(0)
        s = r.integers(0, n, 64).astype(np.int32)
        t = r.integers(0, n, 64).astype(np.int32)
        want = np.asarray(idx.query(s, t))
        # shard the queries across 8 devices; Auto axes let the
        # engine's single-device label gathers propagate the batch
        # sharding (Explicit axes would demand an out_sharding per gather)
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        sq = jax.device_put(jnp.asarray(s), NamedSharding(mesh, P("data")))
        tq = jax.device_put(jnp.asarray(t), NamedSharding(mesh, P("data")))
        got = np.asarray(idx.engine.query(sq, tq))
        fin = np.isfinite(want)
        assert (np.isfinite(got) == fin).all()
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)
        print("ok")
    """)
    assert "ok" in out
