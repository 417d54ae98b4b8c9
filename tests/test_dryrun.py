"""Dry-run machinery: collective parser units + small-mesh lower/compile in a
subprocess (so the main test process keeps its single CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import _shape_bytes, collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCollectiveParser:
    def test_shape_bytes(self):
        assert _shape_bytes("bf16[128,256]") == 128 * 256 * 2
        assert _shape_bytes("f32[16]{0}") == 64
        assert _shape_bytes("(bf16[8,8], f32[4])") == 128 + 16
        assert _shape_bytes("pred[10]") == 10

    def test_collective_classification(self):
        hlo = textwrap.dedent("""
          %ar = bf16[1024]{0} all-reduce(%x), replica_groups={}
          %ag.1 = f32[512,16]{1,0} all-gather(%y), dimensions={1}
          %rs = f32[64]{0} reduce-scatter(%z), dimensions={0}
          %a2a = (f32[32]{0}, f32[32]{0}) all-to-all(%p, %q)
          %cp = bf16[16,16]{1,0} collective-permute(%w)
          %dot = f32[8,8]{1,0} dot(%a, %b)
        """)
        out = collective_bytes(hlo)
        assert out["all-reduce"] == 2 * 1024 * 2  # 2x ring convention
        assert out["all-gather"] == 512 * 16 * 4
        assert out["reduce-scatter"] == 64 * 4
        assert out["all-to-all"] == 2 * 32 * 4
        assert out["collective-permute"] == 16 * 16 * 2


@pytest.mark.slow
def test_small_mesh_train_lowering_subprocess():
    """Lower + compile a reduced arch's train step on an 8-device (2,4) mesh
    and on a (2,2,2) pod mesh; assert collectives exist and it compiles."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import model as M
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.train.train_loop import make_train_step
        from repro.launch.sharding import param_specs, batch_specs
        from repro.launch.dryrun import collective_bytes
        from repro.configs.base import SHAPES
        from repro.launch.mesh import make_mesh, set_mesh

        cfg = get_config('mixtral-8x7b').reduced()
        for shape, axes in [((2,4), ('data','model')), ((2,2,2), ('pod','data','model'))]:
            mesh = make_mesh(shape, axes)
            params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            opt = jax.eval_shape(partial(adamw_init), params)
            pspecs = param_specs(params, mesh)
            ospecs = {'m': pspecs, 'v': pspecs, 'step': NamedSharding(mesh, P())}
            batch = {'tokens': jax.ShapeDtypeStruct((8, 32), jnp.int32),
                     'labels': jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            bspecs = batch_specs(cfg, SHAPES['train_4k'], mesh, batch)
            step = make_train_step(cfg, AdamWConfig())
            ws = lambda t, s: jax.tree_util.tree_map(
                lambda a, b: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=b), t, s)
            with set_mesh(mesh):
                lowered = jax.jit(step).lower(ws(params, pspecs), ws(opt, ospecs), ws(batch, bspecs))
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            assert mem is not None
            coll = collective_bytes(compiled.as_text())
            assert coll['all-reduce'] > 0, coll  # DP grad sync must appear
            print(shape, 'collectives:', {k: v for k, v in coll.items() if v})
        print('DRYRUN_SMALL_OK')
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=540, env=env, cwd=REPO)
    assert "DRYRUN_SMALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


@pytest.mark.slow
def test_decode_small_mesh_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from functools import partial
        from repro.configs import get_config
        from repro.models import model as M
        from repro.launch.sharding import param_specs, state_specs
        from repro.launch.mesh import make_mesh, set_mesh

        cfg = get_config('h2o-danube-3-4b').reduced()
        mesh = make_mesh((2, 4), ('data', 'model'))
        params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
        state = jax.eval_shape(lambda: M.init_decode_state(cfg, 4, 128))
        pspecs = param_specs(params, mesh)
        sspecs = state_specs(cfg, mesh, state)
        ws = lambda t, s: jax.tree_util.tree_map(
            lambda a, b: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=b), t, s)
        tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
        with set_mesh(mesh):
            lowered = jax.jit(partial(M.decode_step, cfg)).lower(
                ws(params, pspecs), ws(state, sspecs), tok)
            lowered.compile()
        print('DECODE_SMALL_OK')
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=540, env=env, cwd=REPO)
    assert "DECODE_SMALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
