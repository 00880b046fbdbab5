"""Distribution substrate tests.

Unit tests for the logical-rules machinery run in-process (pure metadata).
Multi-device behaviour (pjit train step, pipeline parallelism, elastic
restore) runs in a SUBPROCESS with ``--xla_force_host_platform_device_count``
so the main pytest process keeps the single real CPU device (the dry-run is
the only place allowed to fake 512 devices; see the assignment contract).
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spec_for_rules():
    rules = shd.tp_fsdp_rules()
    assert shd.spec_for(("batch", None, "embed_act"), rules) == \
        P(("data",), None, None)
    assert shd.spec_for(("embed", "mlp"), rules) == P("data", "model")
    rules_mp = shd.tp_fsdp_rules(multi_pod=True)
    assert shd.spec_for(("batch", "seq"), rules_mp) == \
        P(("pod", "data"), None)


def test_spec_for_deduplicates_mesh_axes():
    # an axis may appear only once in a PartitionSpec
    rules = {"a": "model", "b": "model"}
    spec = shd.spec_for(("a", "b"), rules)
    assert spec == P("model", None)


def test_shard_noop_without_context():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    y = shd.shard(x, "batch", "embed")
    assert y.shape == x.shape


def _run_sub(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROCESS_OK")
    """)
    # the child is CPU-only: a parent holding a TPU would starve it
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SUBPROCESS_OK" in res.stdout
    return res.stdout


def test_pjit_train_step_on_mesh():
    """Smoke-config train step actually executes SPMD on a 2x2 mesh."""
    _run_sub("""
        from repro.configs import registry
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import specs as S
        from repro.configs.base import ShapeCell
        from repro.train import lm

        cfg = registry.get_config("deepseek_7b", smoke=True)
        mesh = make_debug_mesh(2, 2)
        rules = shd.tp_fsdp_rules()
        with shd.use_sharding(mesh, rules):
            params, opt_state, axes = lm.init_train_state(
                jax.random.key(0), cfg)
            batch = S.concrete_inputs(cfg, ShapeCell("s", 32, 4, "train"))
            step, _ = lm.make_train_step(cfg)
            opt_axes = {"mu": axes, "nu": axes, "count": None}
            in_sh = shd.tree_shardings(
                (axes, opt_axes, {"tokens": ("batch", None)}, None), mesh,
                rules, like=(params, opt_state, batch, jax.random.key(1)))
            p2, o2, m = jax.jit(step, in_shardings=in_sh)(
                params, opt_state, batch, jax.random.key(1))
            assert np.isfinite(float(m["loss"]))
    """, devices=4)


def test_pipeline_parallel_matches_sequential():
    """GPipe schedule == running the stages back to back."""
    _run_sub("""
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply
        n_stages, m, mb, d = 4, 6, 3, 8
        mesh = jax.make_mesh((n_stages,), ("pipe",))
        ks = jax.random.split(jax.random.key(0), n_stages)
        stage_w = jax.vmap(
            lambda k: jax.random.normal(k, (d, d)) * 0.3)(ks)

        def block(w, x):
            return jnp.tanh(x @ w)

        xs = jax.random.normal(jax.random.key(1), (m, mb, d))
        out = pipeline_apply(block, stage_w, xs, mesh, axis="pipe")
        # sequential oracle
        ref = xs
        for s in range(n_stages):
            ref = jax.vmap(lambda x: block(stage_w[s], x))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    """, devices=4)


_PIPELINE_GRID_BODY = """
    from jax.sharding import Mesh
    from repro.distributed.pipeline import pipeline_apply
    mb, d = 3, 8

    def block(w, x):
        return jnp.tanh(x @ w)

    for n_stages in {stages}:
        mesh = jax.make_mesh((n_stages,), ("pipe",))
        ks = jax.random.split(jax.random.key(n_stages), n_stages)
        stage_w = jax.vmap(
            lambda k: jax.random.normal(k, (d, d)) * 0.3)(ks)
        for m in {microbatches}:
            xs = jax.random.normal(jax.random.key(m), (m, mb, d))
            out = pipeline_apply(block, stage_w, xs, mesh, axis="pipe")
            ref = xs
            for s in range(n_stages):
                ref = jax.vmap(lambda x: block(stage_w[s], x))(ref)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
                err_msg=f"S={{n_stages}} M={{m}}")
"""


def test_pipeline_schedule_underfilled():
    """The GPipe schedule with FEWER microbatches than stages — including
    the degenerate M == 1 (a single bubble-dominated pass) — still equals
    the serial layer-stack oracle."""
    _run_sub(_PIPELINE_GRID_BODY.format(stages=(4,),
                                        microbatches=(1, 2, 3)),
             devices=4)


@pytest.mark.slow
def test_pipeline_schedule_grid():
    """Full S x M sweep on a forced-8-device host: M < S, M == S, M == 1
    and M >> S for every stage count."""
    _run_sub(_PIPELINE_GRID_BODY.format(stages=(2, 4, 8),
                                        microbatches=(1, 2, 5, 8, 17)),
             devices=8)


def test_nested_mesh_composes_pipe_and_data():
    """sharding.nested_mesh builds the ('pipe','data','array_row',
    'array_col') mesh, and pipeline_apply(data_axis='data') runs the GPipe
    schedule with each microbatch's batch dim sharded over the data
    replicas INSIDE the same shard_map — equal to the serial oracle."""
    _run_sub("""
        from repro.distributed import sharding as shd
        from repro.distributed.pipeline import pipeline_apply

        mesh = shd.nested_mesh(pipe=4, data=2)
        assert mesh.axis_names == shd.NESTED_AXES
        assert mesh.shape == {"pipe": 4, "data": 2, "array_row": 1,
                              "array_col": 1}

        n_stages, m, mb, d = 4, 3, 4, 8   # mb=4 splits over data=2
        ks = jax.random.split(jax.random.key(0), n_stages)
        stage_w = jax.vmap(
            lambda k: jax.random.normal(k, (d, d)) * 0.3)(ks)

        def block(w, x):
            return jnp.tanh(x @ w)

        xs = jax.random.normal(jax.random.key(1), (m, mb, d))
        out = pipeline_apply(block, stage_w, xs, mesh, axis="pipe",
                             data_axis="data")
        ref = xs
        for s in range(n_stages):
            ref = jax.vmap(lambda x: block(stage_w[s], x))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        # composition guard rails: a sharded tile grid cannot nest
        for bad in (dict(data=2, tile=(2, 2)), dict(pipe=2, tile=(2, 2))):
            try:
                shd.MeshPlan(**bad).validate(8)
            except ValueError:
                pass
            else:
                raise AssertionError(f"{bad} should not validate")
    """, devices=8)


def test_moe_a2a_matches_gather_dispatch():
    """shard_map all-to-all MoE == GSPMD gather dispatch, bit-for-bit
    (no-drop capacity), on a (2 data x 4 model) mesh."""
    _run_sub("""
        import dataclasses
        from repro.configs import registry
        from repro.launch.mesh import make_debug_mesh
        from repro.distributed import sharding as shd
        from repro.models import moe

        cfg = registry.get_config("kimi_k2_1t_a32b", smoke=True)
        cfg = dataclasses.replace(
            cfg, param_dtype=jnp.float32, act_dtype=jnp.float32,
            moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2,
                                    capacity_factor=8.0))
        p, _ = moe.init(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                              jnp.float32) * 0.5
        mesh = make_debug_mesh(2, 4)
        with shd.use_sharding(mesh, shd.tp_fsdp_rules()):
            cfg_g = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, dispatch="gather"))
            cfg_a = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, dispatch="a2a"))
            yg, _ = jax.jit(lambda p, x: moe.apply(p, x, cfg_g))(p, x)
            ya, _ = jax.jit(lambda p, x: moe.apply(p, x, cfg_a))(p, x)
            gr = jax.jit(jax.grad(
                lambda p: moe.apply(p, x, cfg_a)[0].sum()))(p)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(ya),
                                   rtol=1e-5, atol=1e-5)
        assert np.isfinite(float(jnp.linalg.norm(gr["wi"])))
    """, devices=8)


def test_elastic_restore_across_meshes(tmp_path):
    """Save params sharded on a 4-dev mesh, restore onto a 2-dev mesh."""
    _run_sub(f"""
        from repro.checkpoint import store
        from repro.distributed import sharding as shd
        from jax.sharding import NamedSharding

        mesh4 = jax.make_mesh((2, 2), ("data", "model"))
        t = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        t = jax.device_put(t, NamedSharding(mesh4, P("data", "model")))
        store.save(r"{tmp_path}", 1, t)

        mesh2 = jax.make_mesh((2, 1), ("data", "model"))
        sh = {{"w": NamedSharding(mesh2, P("data", "model"))}}
        restored, _ = store.restore(r"{tmp_path}", 1, t, shardings=sh)
        assert restored["w"].sharding.mesh.shape == {{"data": 2, "model": 1}}
        np.testing.assert_array_equal(
            np.asarray(restored["w"]),
            np.arange(64, dtype=np.float32).reshape(8, 8))
    """, devices=4)


def test_relax_spec():
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16, "data": 4}

    spec = shd.relax_spec(P("model", "data"), (50280, 768), FakeMesh())
    assert spec == P(None, "data")
    spec = shd.relax_spec(P("model"), (1600,), FakeMesh())
    assert spec == P("model")
