"""Shared autotune core: telemetry, deadline budgets, swap policy, the
online SearchSupervisor (search -> validate -> hot-swap -> rollback), and
fleet fold-back."""
import math
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.autotune as A
import repro.core.search as S
from repro.autotune import (
    NestTelemetry,
    SearchSupervisor,
    SwapPolicy,
    build_program,
    logit_pipeline_program,
    online_search_task,
    run_supervised,
)
from repro.core import Daisy, TuningDatabase, fingerprint
from repro.core.embedding import embed_nest
from repro.core.recipes import Recipe
from repro.fault import Fault, FaultPlan


def stale_database(prog, backend="xla", measured_us=2500.0):
    """A deliberately mistuned pretuned database: every canonical nest of
    ``prog`` pinned to the slow ``sequential`` recipe."""
    d = Daisy(backend=backend)
    p = d._normalized(prog)
    db = TuningDatabase()
    for nest in p.body:
        db.add(fingerprint(nest), embed_nest(p, nest),
               Recipe(kind="sequential", notes="stale"),
               provenance="stale-pretuned", measured_us=measured_us)
    db.meta["backend"] = backend
    return db


def nest_coords(prog, backend="xla"):
    """(fingerprint, embedding) of the single canonical nest of ``prog``."""
    d = Daisy(backend=backend)
    p = d._normalized(prog)
    assert len(p.body) == 1
    return fingerprint(p.body[0]), embed_nest(p, p.body[0])


def fake_result(fp, emb, cand, cand_us, inc, inc_us, program_key,
                name="logit_pipeline"):
    return {"fingerprint": fp, "embedding": np.asarray(emb).tolist(),
            "recipe": cand.to_json(), "measured_us": cand_us,
            "provenance": "online:test", "incumbent": inc.to_json(),
            "incumbent_us": inc_us, "name": name, "nest_index": 0,
            "program_key": program_key}


class TestTelemetry:
    def test_ema_count_total(self):
        t = NestTelemetry(alpha=0.5)
        t.observe("k", 1.0)
        assert t.ema("k") == 1.0  # first observation seeds the EMA
        t.observe("k", 3.0)
        assert t.ema("k") == pytest.approx(2.0)
        assert t.count("k") == 2
        assert t.snapshot()["k"]["total_s"] == pytest.approx(4.0)

    def test_disabled_is_noop(self):
        t = NestTelemetry(enabled=False)
        t.observe("k", 1.0)
        assert t.ema("k") is None and t.count("k") == 0
        assert t.snapshot() == {}

    def test_hottest_ranks_by_total_time(self):
        t = NestTelemetry()
        for _ in range(10):
            t.observe("warm", 0.01)  # many cheap steps
        t.observe("hot", 1.0)        # one expensive step dominates
        assert [k for k, _ in t.hottest(2)] == ["hot", "warm"]

    def test_reset(self):
        t = NestTelemetry()
        t.observe("k", 1.0)
        t.reset("k")
        assert t.ema("k") is None and t.count("k") == 0


class TestDeadline:
    @staticmethod
    def _fake_measure(calls):
        def fake(nprog, inputs, recipe, repeats=3, interpret=True):
            calls.append(recipe)
            # deterministic pseudo-fitness from the recipe's content
            return 1.0 + (hash(repr(recipe)) % 97) / 10.0
        return fake

    def test_unbounded_and_roomy_deadline_walk_identical_sequences(
            self, monkeypatch):
        seed = Recipe(kind="vectorize")
        calls1, calls2 = [], []
        monkeypatch.setattr(S, "measure_recipe", self._fake_measure(calls1))
        r1 = S.evolve_recipe(None, {}, seed, iterations=3, population=4,
                             rng_seed=5)
        monkeypatch.setattr(S, "measure_recipe", self._fake_measure(calls2))
        r2 = S.evolve_recipe(None, {}, seed, iterations=3, population=4,
                             rng_seed=5, deadline_s=1e6)
        assert r1 == r2
        assert calls1 == calls2  # same RNG walk, same candidates measured

    def test_expired_deadline_returns_partial_best(self, monkeypatch):
        calls = []
        monkeypatch.setattr(S, "measure_recipe", self._fake_measure(calls))
        seed = Recipe(kind="vectorize")
        best, t = S.evolve_recipe(None, {}, seed, iterations=50,
                                  population=8, rng_seed=0, deadline_s=0.0)
        # only the seed was measured before the budget expired
        assert len(calls) == 1 and math.isfinite(t)
        assert best == seed

    def test_seed_nest_threads_deadline(self):
        prog = logit_pipeline_program(vocab=32, slots=2)
        d = Daisy()
        p = d._normalized(prog)
        fp, _emb, recipe, t, prov = d.seed_nest(
            p, p.body[0], search=True, search_iterations=50, population=8,
            repeats=1, deadline_s=0.0)
        # the 50x8 search was cut to the seed measurement: finishes fast
        # and still returns a measured recipe
        assert math.isfinite(t) and recipe is not None


class TestSwapPolicy:
    def test_margin(self):
        p = SwapPolicy(margin=0.1)
        assert p.accepts(89.0, 100.0)        # beats by >10%
        assert not p.accepts(95.0, 100.0)    # inside the margin
        assert not p.accepts(100.0, 100.0)

    def test_non_finite(self):
        p = SwapPolicy()
        assert not p.accepts(float("inf"), 100.0)
        assert not p.accepts(float("nan"), 100.0)
        assert p.accepts(100.0, float("inf"))  # unmeasurable incumbent

    def test_chain(self):
        assert SwapPolicy().chain_for("xla") == ("xla",)
        assert SwapPolicy().chain_for("pallas") == ("pallas", "xla")
        assert SwapPolicy(validate_backends=("xla",)).chain_for("pallas") \
            == ("xla",)


class TestSupervisorDecisions:
    """Swap-policy behaviour driven by synthetic search results (the real
    search path is covered by TestOnlineEndToEnd and the benchmark)."""

    def setup_method(self):
        self.prog = logit_pipeline_program(vocab=32, slots=2)
        self.db = stale_database(self.prog)
        self.fp, self.emb = nest_coords(self.prog)
        self.inc = self.db.lookup_exact(self.fp)

    def _sup(self, **kw):
        kw.setdefault("mode", "sync")
        sup = SearchSupervisor(self.db, **kw)
        key = sup.register(self.prog)
        return sup, key

    def test_winning_candidate_swaps_and_bumps_generation(self):
        sup, key = self._sup(policy=SwapPolicy(margin=0.05))
        gen0 = self.db.generation
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        swaps = sup.poll()
        assert len(swaps) == 1 and not swaps[0].rolled_back
        assert self.db.generation > gen0
        assert self.db.lookup_exact(self.fp).kind == "vectorize"

    def test_worse_candidate_rejected_incumbent_untouched(self):
        sup, key = self._sup(policy=SwapPolicy(margin=0.1))
        gen0 = self.db.generation
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 990.0,
                                     self.inc, 1000.0, key))
        assert sup.poll() == []
        assert sup.rejected and "margin" in sup.rejected[0]["reason"]
        assert self.db.generation == gen0
        assert self.db.lookup_exact(self.fp).kind == "sequential"

    def test_failing_candidate_rejected_by_validation(self):
        plan = FaultPlan([Fault("daisy.compile", "error", key="xla",
                                times=-1)])
        sup, key = self._sup(policy=SwapPolicy(margin=0.05),
                             fault_plan=plan)
        gen0 = self.db.generation
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        assert sup.poll() == []
        assert sup.rejected and "validation" in sup.rejected[0]["reason"]
        assert self.db.generation == gen0
        assert self.db.lookup_exact(self.fp).kind == "sequential"

    def test_degraded_candidate_records_on_engine_degradations(self):
        # first validation rung (pallas_interpret) faulted -> the candidate
        # validates on the xla rung and the degradation is recorded on the
        # engine, exactly like compile_resilient does
        plan = FaultPlan([Fault("daisy.compile", "error",
                                key="pallas_interpret")])
        db = stale_database(self.prog, backend="pallas_interpret")
        sup = SearchSupervisor(db, backend="pallas_interpret", mode="sync",
                               policy=SwapPolicy(margin=0.05),
                               fault_plan=plan)
        key = sup.register(self.prog)
        engine = SimpleNamespace(degradations=[])
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        swaps = sup.poll(engine=engine)
        assert len(swaps) == 1 and swaps[0].degraded_to == "xla"
        assert engine.degradations == [
            ("logit_pipeline", "pallas_interpret", "xla")]

    def test_post_swap_regression_rolls_back_and_quarantines(self):
        sup, key = self._sup(
            policy=SwapPolicy(margin=0.05, rollback_ratio=1.5,
                              rollback_window=3))
        for _ in range(4):  # pre-swap EMA ~1ms
            sup.telemetry.observe(key, 0.001)
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        [rec] = sup.poll()
        gen_after_swap = self.db.generation
        for _ in range(3):  # post-swap steps regress 10x
            sup.telemetry.observe(key, 0.01)
        assert sup.poll() == []
        assert rec.rolled_back
        assert self.db.lookup_exact(self.fp).kind == "sequential"
        assert self.db.generation > gen_after_swap  # un-swap = another bump
        assert self.fp in sup.quarantined

    def test_healthy_swap_watch_disarms_silently(self):
        sup, key = self._sup(
            policy=SwapPolicy(margin=0.05, rollback_ratio=1.5,
                              rollback_window=3))
        for _ in range(4):
            sup.telemetry.observe(key, 0.001)
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        [rec] = sup.poll()
        for _ in range(3):  # post-swap steps improved, as promised
            sup.telemetry.observe(key, 0.0005)
        sup.poll()
        assert not rec.rolled_back and not sup.quarantined
        assert self.db.lookup_exact(self.fp).kind == "vectorize"

    def test_fold_back_merges_and_counts_swaps(self, tmp_path):
        sup, key = self._sup(policy=SwapPolicy(margin=0.05))
        sup._results.put(fake_result(self.fp, self.emb,
                                     Recipe(kind="vectorize"), 100.0,
                                     self.inc, 1000.0, key))
        sup.poll()
        fleet = tmp_path / "fleet.json"
        report = sup.fold_back(fleet)
        assert report["added"] == len(self.db.entries)
        disk = TuningDatabase.load(fleet)
        assert disk.lookup_exact(self.fp).kind == "vectorize"
        assert disk.meta["online_swaps"] == 1
        # a second deployment folding back the same winner composes
        report2 = sup.fold_back(fleet)
        assert report2["added"] == 0


class TestSupervisedOnlineSearch:
    def test_online_search_task_reports_incumbent_and_candidate(self):
        # 1024 vocab rows: the sequential incumbent's fori loop is several
        # times the vectorized chain's time, well outside CPU timing noise
        prog = logit_pipeline_program(vocab=1024, slots=2)
        db = stale_database(prog)
        fp, _ = nest_coords(prog)
        task = {"name": prog.name, "nest_index": 0, "backend": "xla",
                "fingerprint": fp, "iterations": 1, "population": 2,
                "repeats": 3, "deadline_s": 30.0, "program_key": "k",
                "incumbent": db.lookup_exact(fp).to_json(), "program": prog}
        results, quarantined = run_supervised(
            [task], jobs=1, verbose=False, worker=online_search_task)
        assert not quarantined and len(results) == 1
        r = results[0]
        assert r["fingerprint"] == fp and r["program_key"] == "k"
        assert math.isfinite(r["incumbent_us"])
        # the sequential incumbent is far off the pace at this shape: the
        # one-iteration search must already beat it
        assert r["measured_us"] < r["incumbent_us"]

    def test_poison_online_search_is_quarantined_not_raised(self):
        prog = logit_pipeline_program(vocab=32, slots=2)
        fp, _ = nest_coords(prog)
        plan = FaultPlan([Fault("tune.worker", "error", key=fp, times=-1)])
        task = {"name": prog.name, "nest_index": 0, "backend": "xla",
                "fingerprint": fp, "iterations": 1, "population": 2,
                "repeats": 1, "program_key": "k", "incumbent": None,
                "program": prog}
        results, quarantined = run_supervised(
            [task], jobs=1, verbose=False, max_task_retries=1,
            fault_plan=plan, worker=online_search_task)
        assert results == [] and fp in quarantined

    def test_supervisor_survives_poison_round(self):
        prog = logit_pipeline_program(vocab=32, slots=2)
        db = stale_database(prog)
        fp, _ = nest_coords(prog)
        plan = FaultPlan([Fault("tune.worker", "error", key=fp, times=-1)])
        sup = SearchSupervisor(db, mode="sync", fault_plan=plan,
                               max_task_retries=1,
                               policy=SwapPolicy(min_observations=1))
        key = sup.register(prog)
        sup.telemetry.observe(key, 0.01)
        assert sup.maybe_launch() == 1
        sup.poll()
        assert fp in sup.quarantined
        # quarantined nests are never re-launched
        assert sup.maybe_launch() == 0


class TestRegistry:
    def test_build_program_import_coordinates(self):
        p = build_program("import", "repro.autotune:logit_pipeline_program",
                          kwargs={"vocab": 32, "slots": 2})
        assert p.name == "logit_pipeline"
        assert dict((a.name, a.shape) for a in p.arrays)["X"] == (32, 2)

    def test_build_program_import_rejects_bad_name(self):
        with pytest.raises(ValueError, match="module:function"):
            build_program("import", "no-colon-here")

    def test_tools_tune_reexports_are_the_shared_core(self):
        import repro.tools.tune as T

        assert T._tune_nest is A.tune_nest_task
        assert T._run_tasks is A.run_supervised
        assert T._task_key is A.task_key
        assert T._PoolStall is A.PoolStall
        assert T.build_program is A.build_program
        assert T.program_specs is A.program_specs

    def test_spawn_registration_requires_builder(self):
        prog = logit_pipeline_program(vocab=32, slots=2)
        sup = SearchSupervisor(stale_database(prog), mode="spawn")
        with pytest.raises(ValueError, match="builder"):
            sup.register(prog)


class TestOneProcessPerChip:
    """On a TPU this process holds the chip, so no spawn pool may start."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")

    def test_process_pool_refused(self, on_tpu):
        with pytest.raises(RuntimeError, match="holds the TPU"):
            run_supervised([{"fingerprint": "a"}, {"fingerprint": "b"}],
                           jobs=2, verbose=False)

    def test_spawn_supervisor_refused(self, on_tpu):
        with pytest.raises(RuntimeError, match="holds the TPU"):
            SearchSupervisor(TuningDatabase(), mode="spawn")

    def test_in_process_modes_allowed(self, on_tpu):
        assert run_supervised([], jobs=1, verbose=False) == ([], {})
        assert SearchSupervisor(TuningDatabase(), mode="sync").mode == "sync"


class TestOnlineEndToEnd:
    """The full loop against a live engine: stale database -> telemetry ->
    sync search -> validated swap -> bit-identical tokens."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import get_config
        from repro.models import model as M

        cfg = get_config("minicpm-2b").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        prog = logit_pipeline_program(vocab=cfg.vocab, slots=2)
        rng = np.random.default_rng(7)
        aux = {"B": rng.normal(0, 0.5, cfg.vocab).astype(np.float32),
               "S": np.full(cfg.vocab, 1.1, np.float32),
               "G": np.full(cfg.vocab, 0.9, np.float32),
               "F": np.full(cfg.vocab, -1e9, np.float32),
               "K": np.full(cfg.vocab, 1e9, np.float32)}
        prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
                   for n in rng.integers(3, 9, size=6)]
        return cfg, params, prog, aux, prompts

    def _run(self, setup, tuner=None, db=None):
        from repro.serve.engine import ServeConfig, ServingEngine

        cfg, params, prog, aux, prompts = setup
        scfg = ServeConfig(batch_slots=2, max_len=64, max_new_tokens=6)
        eng = ServingEngine(cfg, params, scfg, tuning_db=db,
                            logit_program=prog, logit_inputs=aux,
                            tuner=tuner)
        for p in prompts:
            eng.submit(p)
        return eng, eng.drain()

    def test_adaptive_swap_is_bit_identical(self, setup):
        cfg, params, prog, aux, prompts = setup
        _, baseline = self._run(setup, db=stale_database(prog))

        sup = SearchSupervisor(
            stale_database(prog), mode="sync", check_every=4,
            iterations=1, population=2, repeats=1, deadline_s=30.0,
            policy=SwapPolicy(margin=0.05, min_observations=2))
        eng, adapted = self._run(setup, tuner=sup)
        assert len(sup.swaps) >= 1, \
            f"no swap landed (rejected: {sup.rejected})"
        assert sup.db.lookup_exact(sup.swaps[0].fingerprint).kind != \
            "sequential"
        # the hot-swap changed the lowering, never the tokens
        assert adapted == baseline
        # the engine observed its program's timings under its fingerprint
        assert eng.telemetry.count(eng._telemetry_key) > 0

    def test_tuner_db_mismatch_rejected(self, setup):
        from repro.serve.engine import ServeConfig, ServingEngine

        cfg, params, prog, aux, _ = setup
        sup = SearchSupervisor(stale_database(prog), mode="sync")
        with pytest.raises(ValueError, match="tuner.db"):
            ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=64),
                          tuning_db=TuningDatabase(), tuner=sup,
                          logit_program=prog, logit_inputs=aux)

    def test_unknown_logit_input_rejected(self, setup):
        from repro.serve.engine import ServeConfig, ServingEngine

        cfg, params, prog, aux, _ = setup
        bad = dict(aux, TYPO=np.zeros(cfg.vocab, np.float32))
        with pytest.raises(ValueError, match="TYPO"):
            ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=64),
                          logit_program=prog, logit_inputs=bad)

    def test_wrong_program_shape_rejected(self, setup):
        from repro.serve.engine import ServeConfig, ServingEngine

        cfg, params, _, _, _ = setup
        wrong = logit_pipeline_program(vocab=cfg.vocab, slots=3)  # != slots
        with pytest.raises(ValueError, match="batch_slots"):
            ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=64),
                          logit_program=wrong)


class TestTrainerTelemetry:
    def test_trainer_observes_step_times(self, tmp_path):
        from repro.configs import get_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.train.train_loop import Trainer, TrainerConfig

        cfg = get_config("minicpm-2b").reduced()
        tel = NestTelemetry()
        tr = Trainer(cfg, AdamWConfig(),
                     DataConfig(seq_len=16, global_batch=2, vocab=cfg.vocab),
                     TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=100),
                     telemetry=tel)
        tr.run(3)
        assert tel.count(tr._telemetry_key) == 3
        assert tel.ema(tr._telemetry_key) > 0

    def test_trainer_default_telemetry_disabled(self, tmp_path):
        from repro.configs import get_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.train.train_loop import Trainer, TrainerConfig

        cfg = get_config("minicpm-2b").reduced()
        tr = Trainer(cfg, AdamWConfig(),
                     DataConfig(seq_len=16, global_batch=2, vocab=cfg.vocab),
                     TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=100))
        tr.run(2)
        assert tr.telemetry.count(tr._telemetry_key) == 0
