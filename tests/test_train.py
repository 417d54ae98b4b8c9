"""Training substrate: determinism, checkpoint/restart, schedules, FT."""
import tempfile
import shutil
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import DataConfig, LMDataPipeline
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, lr_at
from repro.optim.compression import compress_grads, decompress_grads
from repro.fault import Heartbeat, StragglerMonitor
from repro.train.checkpoint import CheckpointManager
from repro.train.train_loop import Trainer, TrainerConfig


def test_train_fault_shim_warns_on_import():
    import importlib
    import sys

    sys.modules.pop("repro.train.fault", None)
    with pytest.warns(DeprecationWarning, match="repro.fault"):
        importlib.import_module("repro.train.fault")


CFG = get_config("minicpm-2b").reduced()


def _dcfg(**kw):
    base = dict(seq_len=16, global_batch=4, vocab=CFG.vocab, seed=11)
    base.update(kw)
    return DataConfig(**base)


class TestData:
    def test_batch_is_pure_function_of_step(self):
        p = LMDataPipeline(_dcfg())
        b1, b2 = p.batch_at(5), p.batch_at(5)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(p.batch_at(6)["tokens"], b1["tokens"])

    def test_labels_shift(self):
        p = LMDataPipeline(_dcfg(source="synthetic"))
        b = p.batch_at(0)
        assert b["tokens"].shape == b["labels"].shape == (4, 16)

    def test_sharding_partitions_batch(self):
        full = LMDataPipeline(_dcfg()).batch_at(3)["tokens"]
        s0 = LMDataPipeline(_dcfg(shard_index=0, shard_count=2)).batch_at(3)["tokens"]
        s1 = LMDataPipeline(_dcfg(shard_index=1, shard_count=2)).batch_at(3)["tokens"]
        assert s0.shape[0] == s1.shape[0] == 2
        assert not np.array_equal(s0, s1)

    def test_prefetch_iterator_order(self):
        p = LMDataPipeline(_dcfg())
        p.start(7)
        steps = [p.next()[0] for _ in range(3)]
        p.stop()
        assert steps == [7, 8, 9]

    def test_memmap_source(self, tmp_path):
        toks = np.arange(10_000, dtype=np.uint32) % 97
        f = tmp_path / "tokens.bin"
        toks.tofile(f)
        p = LMDataPipeline(_dcfg(source="memmap", path=str(f)))
        b = p.batch_at(0)
        assert b["tokens"].max() < CFG.vocab


class TestOptim:
    def test_wsd_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, schedule="wsd")
        lrs = [float(lr_at(cfg, s)) for s in range(100)]
        assert lrs[0] < 0.2            # warmup starts low
        assert abs(lrs[50] - 1.0) < 1e-5   # stable plateau
        assert lrs[99] < lrs[89]       # decay at the end

    def test_nan_grads_skip_step(self):
        p = {"w": jnp.ones((4,))}
        st = adamw_init(p)
        g = {"w": jnp.full((4,), jnp.nan)}
        cfg = AdamWConfig()
        p2, st2, m = adamw_update(cfg, p, g, st)
        assert bool(m["skipped"])
        np.testing.assert_array_equal(np.asarray(p2["w"]), np.ones(4))

    def test_grad_clip(self):
        p = {"w": jnp.zeros((4,))}
        st = adamw_init(p)
        g = {"w": jnp.full((4,), 100.0)}
        _, _, m = adamw_update(AdamWConfig(grad_clip=1.0), p, g, st)
        assert float(m["grad_norm"]) > 1.0  # reported pre-clip

    def test_int8_compression_error_feedback(self):
        g = {"w": jnp.linspace(-1, 1, 128)}
        comp, scales, res = compress_grads(g, None, "int8")
        deco = decompress_grads(comp, scales, "int8")
        err = float(jnp.abs(deco["w"] - g["w"]).max())
        assert err < 1e-2
        assert res is not None and float(jnp.abs(res["w"]).max()) < 1e-2

    def test_bf16_compression(self):
        g = {"w": jnp.linspace(-1, 1, 64)}
        comp, _, _ = compress_grads(g, None, "bf16")
        assert comp["w"].dtype == jnp.bfloat16


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        mgr.save(10, tree)
        step, got, _ = mgr.restore(tree)
        assert step == 10
        np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))

    def test_keep_last_k(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        t = {"a": jnp.zeros(())}
        for s in (1, 2, 3, 4):
            mgr.save(s, t)
        assert mgr.steps() == [3, 4]

    def test_missing_key_raises(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"a": jnp.zeros((2,))})
        with pytest.raises(KeyError):
            mgr.restore({"a": jnp.zeros((2,)), "b": jnp.zeros((3,))})


@pytest.mark.slow
class TestTrainerFT:
    def test_resume_is_bit_exact(self, tmp_path):
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=4, log_every=100)
        t1 = Trainer(CFG, ocfg, _dcfg(), tcfg)
        t1.run(8)  # checkpoints at 4 and 8
        ref = jax.tree_util.tree_map(np.asarray, t1.params)

        t2 = Trainer(CFG, ocfg, _dcfg(), tcfg)
        assert t2.try_restore()
        assert t2.step == 8
        # continue both for 2 steps: identical trajectories
        t1.run(2)
        t2.run(2)
        for a, b in zip(jax.tree_util.tree_leaves(t1.params),
                        jax.tree_util.tree_leaves(t2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_injected_failure_recovers(self, tmp_path):
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=3)
        t = Trainer(CFG, ocfg, _dcfg(), tcfg)
        hist = t.run_resilient(8, fail_at=5)
        assert t.step == 8

    def test_straggler_monitor(self):
        m = StragglerMonitor(threshold=2.0)
        for s in range(10):
            m.observe(s, 1.0)
        assert m.observe(10, 5.0)
        assert m.flagged and m.flagged[0][0] == 10

    def test_heartbeat(self, tmp_path):
        hb = Heartbeat(tmp_path / "hb.json", interval=0.05)
        hb.start()
        time.sleep(0.2)
        hb.stop()
        age = Heartbeat.age(tmp_path / "hb.json")
        assert age is not None and age < 5.0


@pytest.mark.slow
def test_elastic_remesh_subprocess():
    """Save on a (2,2) mesh, restore + lower onto (2,4): checkpoints are
    device-count agnostic (elastic scaling)."""
    import subprocess, sys, textwrap, os

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import model as M
        from repro.launch.sharding import param_specs
        from repro.train.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh, set_mesh

        cfg = get_config('minicpm-2b').reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(5, params)

        for shape, axes in [((2, 2), ('data','model')), ((2, 4), ('data','model')),
                            ((2, 2, 2), ('pod','data','model'))]:
            mesh = make_mesh(shape, axes, devices=jax.devices()[:int(np.prod(shape))])
            specs = param_specs(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))), mesh)
            _, restored, _ = mgr.restore(params)
            placed = jax.tree_util.tree_map(jax.device_put, restored, specs)
            batch = {'tokens': jnp.zeros((4, 8), jnp.int32)}
            with set_mesh(mesh):
                logits = jax.jit(lambda p, b: M.forward(cfg, p, b))(placed, batch)
            assert logits.shape == (4, 8, cfg.vocab)
            print('mesh', shape, 'ok')
        print('ELASTIC_OK')
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=540, env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "ELASTIC_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
