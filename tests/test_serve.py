"""Serving engine: request handles, batched continuous decode, bucketed
admission, pipelined dispatch, traffic generator + percentile math."""
import warnings
from functools import partial

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import model as M
from repro.serve import RequestHandle, ServeConfig, ServingEngine, prefill_buckets


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("minicpm-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def greedy_reference(cfg, params, prompt, n, max_len=64):
    """The old per-request loop: exact-length batch-1 prefill + greedy
    argmax decode with a host sync per token."""
    decode = jax.jit(partial(M.decode_step, cfg))
    st = M.init_decode_state(cfg, 1, max_len, ring=False)
    logits, st = decode(params, st, jnp.asarray(prompt[None, :]))
    toks, last = [], logits[:, -1]
    for _ in range(n):
        t = int(jnp.argmax(last[0]))
        toks.append(t)
        last, st = decode(params, st, jnp.full((1, 1), t, jnp.int32))
        last = last[:, -1]
    return toks


# ---------------------------------------------------------------------------
# request lifecycle API
# ---------------------------------------------------------------------------
class TestHandles:
    def test_submit_returns_handle(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=3))
        h = eng.submit(np.array([1, 2, 3], np.int32))
        assert isinstance(h, RequestHandle)
        assert not h.done and h.tokens == []
        assert h.result() == h.tokens and h.done and len(h.tokens) == 3

    def test_step_and_drain(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=4))
        h1 = eng.submit(np.array([1, 2], np.int32))
        h2 = eng.submit(np.array([3, 4, 5], np.int32))
        assert eng.step() > 0  # something live after one iteration
        out = eng.drain()
        assert h1.done and h2.done
        assert out[h1.rid] == h1.tokens and out[h2.rid] == h2.tokens

    def test_streaming_callback(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=4))
        seen = []
        h = eng.submit(np.array([5, 6, 7], np.int32),
                       on_token=lambda hh, t: seen.append((hh.rid, t)))
        got = h.result()
        assert [t for _, t in seen] == got
        assert all(r == h.rid for r, _ in seen)

    def test_legacy_submit_and_run_deprecated(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=5))
        with pytest.warns(DeprecationWarning):
            eng.submit(0, np.array([1, 2, 3], np.int32))
        eng.submit(np.array([9, 8, 7, 6], np.int32), rid=1)
        eng.submit(np.array([4, 4], np.int32), rid=2)  # more requests than slots
        with pytest.warns(DeprecationWarning):
            out = eng.run()
        assert set(out) == {0, 1, 2}
        assert all(len(v) == 5 for v in out.values())

    def test_auto_rids_unique(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=2))
        hs = [eng.submit(np.array([i + 1], np.int32)) for i in range(3)]
        assert len({h.rid for h in hs}) == 3
        out = eng.drain()
        assert set(out) == {h.rid for h in hs}


# ---------------------------------------------------------------------------
# batched decode correctness
# ---------------------------------------------------------------------------
class TestBatchedDecode:
    def test_greedy_matches_per_request_loop(self, setup):
        """Continuous batching must be token-for-token identical to the old
        per-request batch-1 loop (bucketed prefill + batched decode are
        bit-exact)."""
        cfg, params = setup
        prompts = [np.array([3, 1, 4, 1, 5], np.int32),
                   np.array([9, 8, 7], np.int32),
                   np.array([2, 2, 2, 2, 2, 2, 2], np.int32),
                   np.array([6], np.int32),
                   np.array([1, 2, 3, 4], np.int32)]  # > batch_slots
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=4, max_len=64, max_new_tokens=6))
        hs = [eng.submit(p) for p in prompts]
        eng.drain()
        for h, p in zip(hs, prompts):
            assert h.tokens == greedy_reference(cfg, params, p, 6), h.rid

    def test_greedy_is_deterministic(self, setup):
        cfg, params = setup
        outs = []
        for _ in range(2):
            eng = ServingEngine(cfg, params, ServeConfig(max_len=64, max_new_tokens=4))
            outs.append(eng.submit(np.array([5, 6, 7], np.int32)).result())
        assert outs[0] == outs[1]

    def test_pipeline_depth_invariant(self, setup):
        """The dispatch-ahead distance must not change greedy outputs."""
        cfg, params = setup
        prompt = np.array([3, 1, 4], np.int32)
        outs = []
        for depth in (0, 1, 3):
            eng = ServingEngine(
                cfg, params,
                ServeConfig(batch_slots=2, max_len=64, max_new_tokens=5,
                            pipeline_depth=depth))
            outs.append(eng.submit(prompt).result())
        assert outs[0] == outs[1] == outs[2]

    def test_eos_slot_refill_mid_stream(self, setup):
        """eos in one slot while others continue: the finished slot is
        refilled from the queue and nobody else's tokens change."""
        cfg, params = setup
        prompts = [np.array([3, 1, 4, 1, 5], np.int32),
                   np.array([9, 8, 7], np.int32),
                   np.array([2, 7, 1, 8], np.int32)]
        refs = [greedy_reference(cfg, params, p, 8) for p in prompts]
        # pick the token request 0 emits mid-stream as the eos id; requests
        # 1/2 must not emit it anywhere or they'd legitimately stop early
        eos = refs[0][3]
        assert eos not in refs[1] and eos not in refs[2], "test prompt collision"
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=64,
                                        max_new_tokens=8, eos_id=eos))
        hs = [eng.submit(p) for p in prompts]
        out = eng.drain()
        assert hs[0].tokens == refs[0][:4]  # stopped at the eos token
        assert hs[1].tokens == refs[1]      # unaffected neighbours
        assert hs[2].tokens == refs[2]      # admitted into the freed slot
        assert set(out) == {h.rid for h in hs}

    def test_audio_engine_runs(self):
        cfg = get_config("seamless-m4t-large-v2").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        eng = ServingEngine(cfg, params, ServeConfig(max_new_tokens=3, max_len=32))
        assert len(eng.submit(np.array([1, 2], np.int32)).result()) == 3

    def test_temperature_sampling_path(self, setup):
        cfg, params = setup
        eng = ServingEngine(
            cfg, params,
            ServeConfig(batch_slots=2, max_len=64, max_new_tokens=4,
                        temperature=1.0, seed=7))
        h1 = eng.submit(np.array([5, 6, 7], np.int32))
        h2 = eng.submit(np.array([1, 2], np.int32))
        out = eng.drain()
        assert len(h1.tokens) == 4 and len(h2.tokens) == 4
        assert out[h1.rid] == h1.tokens


# ---------------------------------------------------------------------------
# bucketed admission
# ---------------------------------------------------------------------------
class TestBuckets:
    def test_bucket_table(self):
        assert prefill_buckets(512, 16) == (16, 32, 64, 128, 256, 512)
        assert prefill_buckets(96, 16) == (16, 32, 64, 96)
        assert prefill_buckets(8, 16) == (8,)

    def test_prompt_longer_than_largest_bucket(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=32, max_new_tokens=2))
        with pytest.raises(ValueError, match="exceeds the largest prefill bucket"):
            eng.submit(np.arange(1, 40, dtype=np.int32))

    def test_prompt_plus_max_new_overflows_cache(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=32, max_new_tokens=16))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.arange(1, 30, dtype=np.int32))

    def test_empty_prompt_rejected(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, ServeConfig(max_len=64))
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit(np.array([], np.int32))

    def test_empty_queue_is_idle(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, ServeConfig(max_len=64, max_new_tokens=2))
        assert eng.step() == 0
        assert eng.drain() == {}

    def test_bucketed_prefill_matches_exact(self, setup):
        """A prompt that needs padding up to a bucket must decode exactly
        like the exact-length prefill (padded rows masked + overwritten)."""
        cfg, params = setup
        prompt = np.array([11, 3, 9], np.int32)  # pads to the 16 bucket
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=1, max_len=64, max_new_tokens=6))
        assert eng.submit(prompt).result() == greedy_reference(cfg, params, prompt, 6)

    def test_recurrent_families_prefill_exact(self):
        """hybrid/ssm carry token-recurrent state: padded prompt tokens
        would pollute it, so admission uses the exact length."""
        cfg = get_config("xlstm-350m").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(2))
        eng = ServingEngine(cfg, params, ServeConfig(max_len=32, max_new_tokens=2))
        assert eng._bucket_for(5) == 5
        assert len(eng.submit(np.array([1, 2, 3, 4, 5], np.int32)).result()) == 2


# ---------------------------------------------------------------------------
# deployment context (shared engine/trainer boilerplate)
# ---------------------------------------------------------------------------
class TestDeploymentContext:
    def test_engine_and_trainer_share_warm_db(self, setup, tmp_path):
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.train.train_loop import Trainer, TrainerConfig

        cfg, params = setup
        eng = ServingEngine(cfg, params, ServeConfig(max_len=32))
        tr = Trainer(cfg, AdamWConfig(),
                     DataConfig(seq_len=8, global_batch=2, vocab=cfg.vocab),
                     TrainerConfig(ckpt_dir=str(tmp_path)))
        # both fall back to the one shared per-backend deployment database
        assert eng.tuning_db is tr.tuning_db

    def test_jitted_fns_shared_across_engines(self, setup):
        cfg, params = setup
        e1 = ServingEngine(cfg, params, ServeConfig(max_len=32))
        e2 = ServingEngine(cfg, params, ServeConfig(max_len=32))
        assert e1._step_greedy is e2._step_greedy
        assert e1._decode is e2._decode

    def test_place_without_mesh_is_identity(self, setup):
        from repro.models.lowering import deployment_context

        cfg, params = setup
        ctx = deployment_context(cfg, params)
        assert ctx.params is params
        tree = {"x": jnp.ones((2,))}
        assert ctx.place(tree) is tree


# ---------------------------------------------------------------------------
# traffic generator + percentile math (bench_serve units)
# ---------------------------------------------------------------------------
class TestTraffic:
    def test_traffic_deterministic_under_seed(self):
        from benchmarks.bench_serve import make_traffic

        a = make_traffic(12, 50.0, (4, 8, 16), 100, seed=3)
        b = make_traffic(12, 50.0, (4, 8, 16), 100, seed=3)
        c = make_traffic(12, 50.0, (4, 8, 16), 100, seed=4)
        assert [t for t, _ in a] == [t for t, _ in b]
        assert all((pa == pb).all() for (_, pa), (_, pb) in zip(a, b))
        assert [t for t, _ in a] != [t for t, _ in c]
        # open loop: arrivals strictly increasing, lengths from the mix
        times = [t for t, _ in a]
        assert times == sorted(times) and times[0] > 0
        assert {len(p) for _, p in a} <= {4, 8, 16}

    def test_percentile_math(self):
        from benchmarks.bench_serve import percentile

        vals = [10.0, 20.0, 30.0, 40.0]
        assert percentile(vals, 50) == 25.0  # linear interpolation
        assert percentile(vals, 0) == 10.0
        assert percentile(vals, 100) == 40.0
        assert percentile(vals, 99) == pytest.approx(39.7)
        assert percentile([7.0], 99) == 7.0
        assert percentile(np.arange(1, 101, dtype=float), 50) == 50.5
        assert percentile(np.arange(1, 101, dtype=float), 99) == pytest.approx(99.01)
        with pytest.raises(ValueError):
            percentile([], 50)

    def test_handle_result_on_idle_engine_raises(self, setup):
        cfg, params = setup
        h = RequestHandle(rid=0, prompt=np.array([1], np.int32))
        with pytest.raises(RuntimeError, match="idle"):
            h.result()


def test_no_deprecation_from_new_api(setup):
    """The new lifecycle must be warning-free (run()/legacy submit are the
    only deprecated surfaces)."""
    cfg, params = setup
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        eng = ServingEngine(cfg, params, ServeConfig(max_len=64, max_new_tokens=2))
        h = eng.submit(np.array([1, 2], np.int32))
        eng.drain()
    assert h.done
