"""launch/sharding.py: the framework-level DP/TP/EP/SP spec planner.

The rule functions depend only on ``mesh.shape`` / ``mesh.axis_names``, so a
lightweight fake mesh drives the divisibility and fallback logic at sizes no
host-device mesh could provide; ``NamedSharding`` construction is patched to
pass the spec through.  A final integration test places real parameters on a
real mesh over whatever devices exist.
"""
from dataclasses import dataclass

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding
from repro.launch.mesh import dp_axes, make_mesh, set_mesh
from repro.launch.sharding import (
    _add_fsdp,
    _param_rule,
    batch_specs,
    param_specs,
    replicated,
    state_specs,
)


@dataclass
class FakeMesh:
    shape: dict
    axis_names: tuple


MESH = FakeMesh({"data": 4, "model": 4}, ("data", "model"))
POD_MESH = FakeMesh({"pod": 2, "data": 4, "model": 4}, ("pod", "data", "model"))


@pytest.fixture
def spec_passthrough(monkeypatch):
    monkeypatch.setattr(sharding, "NamedSharding", lambda mesh, spec: spec)


class Leaf:
    def __init__(self, *shape):
        self.shape = tuple(shape)
        self.ndim = len(shape)


# ---------------------------------------------------------------------------
# dp_axes / mesh helpers
# ---------------------------------------------------------------------------
class TestMeshHelpers:
    def test_dp_axes_without_pod(self):
        assert dp_axes(MESH) == ("data",)

    def test_dp_axes_with_pod(self):
        assert dp_axes(POD_MESH) == ("pod", "data")

    def test_set_mesh_context_manager(self):
        mesh = make_mesh((jax.device_count(),), ("data",))
        with set_mesh(mesh):
            assert jax.sharding.get_abstract_mesh().axis_names == ("data",)

    def test_make_mesh_axes_are_auto(self):
        from jax.sharding import AxisType

        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


# ---------------------------------------------------------------------------
# parameter rules: divisibility fallbacks, EP vs TP
# ---------------------------------------------------------------------------
class TestParamRules:
    def test_column_parallel_divisible(self):
        assert _param_rule("layers/0/wq", (256, 512), MESH) == P(None, "model")

    def test_column_parallel_indivisible_replicates(self):
        assert _param_rule("layers/0/wq", (256, 510), MESH) == P(None, None)

    def test_row_parallel(self):
        assert _param_rule("layers/0/wo", (512, 256), MESH) == P("model", None)

    def test_row_parallel_indivisible_replicates(self):
        assert _param_rule("layers/0/wo", (510, 256), MESH) == P(None, None)

    def test_expert_split_ep_when_divisible(self):
        # E=8 divides model=4 -> expert parallel on the expert dim
        spec = _param_rule("ffn/wg", (8, 256, 1024), MESH)
        assert spec == P("model", None, None)

    def test_expert_split_tp_fallback(self):
        # E=6 does not divide model=4 -> TP on the trailing feature dim
        assert _param_rule("ffn/wg", (6, 256, 1024), MESH) == P(None, None, "model")
        # ... and wd (row-parallel) shards its contracting dim instead
        assert _param_rule("ffn/wd", (6, 1024, 256), MESH) == P(None, "model", None)

    def test_embed_vocab_vs_feature_parallel(self):
        assert _param_rule("embed", (32000, 256), MESH) == P("model", None)
        assert _param_rule("embed", (32001, 256), MESH) == P(None, "model")
        assert _param_rule("embed", (32001, 255), MESH) == P(None, None)

    def test_gqa_head_mismatch_shards_contracting_dim(self):
        cfg = get_config("minicpm-2b").reduced()
        # n_heads not divisible by model axis -> row-parallel wq instead of
        # the head-flat output dim (the involuntary-remat trap)
        mesh = FakeMesh({"data": 1, "model": 3}, ("data", "model"))
        if cfg.n_heads % 3 != 0 and cfg.d_model % 3 == 0:
            spec = _param_rule("layers/0/wq", (cfg.d_model, 512), mesh, cfg)
            assert spec == P("model", None)

    def test_norms_replicated(self):
        assert _param_rule("layers/0/ln1", (256,), MESH) == P(None)

    def test_modelless_mesh_replicates_params(self):
        # a pure-DP mesh (the canonical-program column mesh) has no 'model'
        # axis: every TP rule must fall back to replication, never emit a
        # spec naming the missing axis or crash
        dp_only = FakeMesh({"data": 4}, ("data",))
        cfg = get_config("minicpm-2b").reduced()
        for path, shape in [("layers/0/wq", (256, 512)),
                            ("layers/0/wo", (512, 256)),
                            ("embed", (32000, 256)),
                            ("ffn/wg", (8, 256, 1024))]:
            spec = _param_rule(path, shape, dp_only, cfg)
            assert all(e is None for e in spec), (path, spec)

    def test_fsdp_adds_one_dp_dim(self):
        spec = _add_fsdp(P(None, "model"), (256, 512), MESH)
        assert spec == P("data", "model")

    def test_fsdp_skips_indivisible(self):
        spec = _add_fsdp(P(None, "model"), (253, 512), MESH)
        assert spec == P(None, "model")  # 253 % 4 != 0 and last dim taken

    def test_fsdp_skips_scanned_stack_dim(self):
        # leading dim of a scanned (L, ...) stack must not be sharded
        spec = _add_fsdp(P(None, None, "model"), (4, 256, 512), MESH)
        assert spec == P(None, "data", "model")

    def test_fsdp_pod_mesh_uses_both_dp_axes(self):
        spec = _add_fsdp(P(None, "model"), (256, 512), POD_MESH)
        assert spec == P(("pod", "data"), "model")


# ---------------------------------------------------------------------------
# batch / state specs (SP fallback)
# ---------------------------------------------------------------------------
class TestBatchStateSpecs:
    def test_batch_divisible_shards_leading(self, spec_passthrough):
        specs = batch_specs(None, None, MESH, {"tokens": Leaf(8, 128)})
        assert specs["tokens"] == P(("data",), None)

    def test_batch_indivisible_replicates(self, spec_passthrough):
        specs = batch_specs(None, None, MESH, {"tokens": Leaf(6, 128)})
        assert specs["tokens"] == P(None, None)

    def test_kv_cache_dp_plus_model(self, spec_passthrough):
        # (L, B, KV, S, dh): batch -> data, the KV heads -> model
        specs = state_specs(None, MESH, {"kv": Leaf(2, 8, 4, 64, 32)})
        assert specs["kv"] == P(None, ("data",), "model", None, None)

    def test_kv_cache_sp_fallback_batch1(self, spec_passthrough):
        # batch=1 long-context decode: shard the cache *sequence* over DP
        specs = state_specs(None, MESH, {"kv": Leaf(2, 1, 4, 64, 32)})
        assert specs["kv"] == P(None, None, "model", ("data",), None)

    def test_memory_state(self, spec_passthrough):
        specs = state_specs(None, MESH, {"memory": Leaf(8, 77, 256)})
        assert specs["memory"] == P(("data",), None, "model")

    def test_scalars_replicated(self, spec_passthrough):
        specs = state_specs(None, MESH, {"pos": Leaf()})
        assert specs["pos"] == P()

    def test_replicated_helper(self, spec_passthrough):
        specs = replicated(MESH, {"x": Leaf(3, 4)})
        assert specs["x"] == P(None, None)


# ---------------------------------------------------------------------------
# integration: real mesh, real params, engine/trainer placement
# ---------------------------------------------------------------------------
class TestPlacement:
    def test_param_specs_places_real_params(self):
        from repro.models import model as M

        cfg = get_config("minicpm-2b").reduced()
        n = jax.device_count()
        mesh = make_mesh((1, n), ("data", "model"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        shapes = jax.eval_shape(lambda p: p, params)
        specs = param_specs(shapes, mesh, cfg=cfg)
        placed = jax.device_put(params, specs)
        leaves = jax.tree_util.tree_leaves(placed)
        assert all(hasattr(l.sharding, "spec") for l in leaves)

    def test_engine_on_dp_only_mesh(self):
        # the mesh the sharded-canonical path hands out (no model axis)
        from repro.models import model as M
        from repro.serve import ServeConfig, ServingEngine

        cfg = get_config("minicpm-2b").reduced()
        mesh = make_mesh((jax.device_count(),), ("data",))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(
            cfg, params, ServeConfig(batch_slots=1, max_len=32,
                                     max_new_tokens=2), mesh=mesh)
        h = eng.submit(np.array([1, 2], np.int32))
        assert len(h.result()) == 2

    def test_engine_with_mesh_generates(self):
        from repro.models import model as M
        from repro.serve import ServeConfig, ServingEngine

        cfg = get_config("minicpm-2b").reduced()
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(
            cfg, params, ServeConfig(batch_slots=1, max_len=32,
                                     max_new_tokens=3), mesh=mesh)
        out = eng.submit(np.array([1, 2, 3], np.int32)).result()
        assert len(out) == 3
        # mesh placement must not change greedy decoding
        eng2 = ServingEngine(
            cfg, params, ServeConfig(batch_slots=1, max_len=32,
                                     max_new_tokens=3))
        assert eng2.submit(np.array([1, 2, 3], np.int32)).result() == out

    @pytest.mark.slow
    def test_trainer_with_mesh_steps(self, tmp_path):
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.train.train_loop import Trainer, TrainerConfig

        cfg = get_config("minicpm-2b").reduced()
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        dcfg = DataConfig(seq_len=16, global_batch=4, vocab=cfg.vocab, seed=1)
        tr = Trainer(cfg, AdamWConfig(), dcfg,
                     TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=100),
                     mesh=mesh)
        m_leaves = jax.tree_util.tree_leaves(tr.opt_state["m"])
        assert all(hasattr(l, "sharding") for l in m_leaves)
        hist = tr.run(2)
        assert len(hist) == 2 and np.isfinite(hist[-1]["loss"])

    @pytest.mark.slow
    def test_run_resilient_on_mesh_restores_placement(self, tmp_path):
        """restart-from-checkpoint on a sharded mesh: the restored params
        and AdamW moments must come back mesh-placed (not host arrays), and
        the recovered run must reach the target step with finite loss."""
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.train.train_loop import Trainer, TrainerConfig

        cfg = get_config("minicpm-2b").reduced()
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        dcfg = DataConfig(seq_len=16, global_batch=4, vocab=cfg.vocab, seed=1)
        tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=2)
        tr = Trainer(cfg, AdamWConfig(), dcfg, tcfg, mesh=mesh)
        hist = tr.run_resilient(5, fail_at=3)  # checkpoint at 2, crash at 3
        assert tr.step == 5 and np.isfinite(hist[-1]["loss"])
        # the restore path must hand back mesh-placed arrays: a fresh trainer
        # restored from the surviving checkpoint carries exactly the
        # construction-time shardings (stepping afterwards may legitimately
        # normalize specs, so the assertion sits right after try_restore)
        tr2 = Trainer(cfg, AdamWConfig(), dcfg, tcfg, mesh=mesh)
        want = {l.sharding for l in jax.tree_util.tree_leaves(tr2.params)}
        assert tr2.try_restore() and tr2.step >= 2
        got = {l.sharding for l in jax.tree_util.tree_leaves(tr2.params)}
        assert got == want
        for moments in (tr2.opt_state["m"], tr2.opt_state["v"]):
            for l in jax.tree_util.tree_leaves(moments):
                assert l.sharding in want
