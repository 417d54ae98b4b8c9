"""The Mellum2 serving cell and the np PolyBench cell: both load through the
harness; on the CPU at the architecture's ``reduced()`` widths the plain
reference agrees with ``model.forward`` and with the engine's own programs
(a padded prefill, then decode through the rings of the sliding layers,
teacher-forced), a whole run is correct, its traced run reports the new
per-layer metrics from the engine's counters, and the control (the
reference in float8) and each fault planted in the served path come out
not correct: the window ignored
on the sliding layers, plain RoPE on the full layers, a held expert left
out, and gates not renormalized."""
import dataclasses
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from mellum_cell import CELL, SEED, run, small_cell

from bench import expert_counts, harness, serving, trace_reduce
from bench.trace_reduce import Event, Trace
from repro.core import spans
from repro.models import layers as L
from repro.models import model as M

E2E = {"setup_s", "itl_p50_ms", "tokens_per_s"}
PER_LAYER = {"idle_share.serve", "serve_mfu.moe"}


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"flops_per_s": 1e12,
                                                            "bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)


def test_both_cells_load_with_their_metrics():
    mel = harness.load_cell(CELL)
    assert mel.chips == 1 and mel.config["builder"] == "serving"
    assert {m["name"] for m in mel.end_to_end} == E2E
    assert {m["name"] for m in mel.per_layer} == PER_LAYER
    cfg = serving.model_config(mel.config)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.n_experts, cfg.held_experts) == (
        28, 2304, 98304, 64, 16)
    assert cfg.layer_types == ("sliding",) * 3 + ("full",) and cfg.window == 1024
    assert mel.config["num_experts"] == 64 and mel.config["reduced"] == ["experts_held"]
    np_cell = harness.load_cell("polybench-xl.np")
    assert np_cell.traffic["variant"] == "np"
    assert {m["name"] for m in np_cell.end_to_end} == {"run_ms_geomean", "setup_s"}
    assert {m["name"] for m in np_cell.per_layer} == {
        "pass_pipeline_s", "db_recipe_share", "roofline_share", "idle_share", "mfu"}
    progs = harness.build_programs(np_cell.config, np_cell.traffic)
    assert len(progs) == 15


def small_model():
    cell = small_cell()
    cfg = serving.model_config(cell.config)
    return cell, cfg, serving.make_weights(cfg, 11)


def test_reference_agrees_with_the_model_forward():
    """At float32 on the CPU both sides compute the same equations in
    another order (a period scan, dropless grouped products and fused
    attention against per-expert products and blocked softmax): they agree
    to float32 round-off, 1e-4 of the logits' range, far below what a
    window, a rotary scaling or an expert moves them (over 1e-2)."""
    cell, cfg, params = small_model()
    m = cell.config["model"]
    tokens = np.random.default_rng(3).integers(0, cfg.vocab, 64).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(cell.reference(params, tokens, m))
        got = np.asarray(M.forward(cfg, params, {"tokens": jnp.asarray(tokens)[None]})[0])
        scale = np.abs(ref).max()
        assert np.abs(got - ref).max() <= 1e-4 * scale
        for kw in ({"window": None}, {"yarn": False}, {"drop_expert": 1},
                   {"renormalize": False}):
            alt = np.asarray(cell.reference(params, tokens, m, **kw))
            assert np.abs(alt - ref).max() > 1e-2 * scale, kw


def test_engine_programs_agree_with_the_reference_through_the_rings():
    """The engine's prefill program (a prompt of 40 padded to its bucket of
    64) and then its slot-batched decode step, teacher-forced for 40 more
    tokens on two slots, give the reference's logits at every position to
    1e-4 of their range: the sliding layers' rings (16 slots) wrap several
    times, the full layer's cache holds every position."""
    cell, cfg, params = small_model()
    m = cell.config["model"]
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, cfg.vocab, 80).astype(np.int32) for _ in range(2)]
    prefill = jax.jit(partial(M.decode_step, cfg))
    step = jax.jit(partial(M.decode_slots, cfg))
    states = M.init_slot_states(cfg, 2, 128)
    got = [[], []]
    for i, seq in enumerate(seqs):
        toks = np.zeros((1, 64), np.int32)
        toks[0, :40] = seq[:40]
        st = M.init_decode_state(cfg, 1, 128, ring=False)
        logits, st = prefill(params, st, jnp.asarray(toks), jnp.int32(40))
        # one stack (periods, 1, KV, S, Dh) per position of the period
        assert [k.shape[3] for k, _ in st["periods"]] == [16, 16, 16, 128]
        got[i].append(np.asarray(logits[0, :40]))
        states = M.write_slot(states, i, st)
    for t in range(40, 79):
        logits, states = step(params, states, jnp.asarray([s[t] for s in seqs]))
        for i in range(2):
            got[i].append(np.asarray(logits[i])[None])
    assert int(states["counts"][0]) > 0
    with jax.default_matmul_precision("highest"):
        for i, seq in enumerate(seqs):
            ref = np.asarray(cell.reference(params, seq[:79], m))
            g = np.concatenate(got[i])
            assert np.abs(g - ref).max() <= 1e-4 * np.abs(ref).max()


def test_sound_run_is_correct_and_compiles_nothing_in_the_window(tmp_path):
    r = run(small_cell())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == E2E
    rec = json.loads((tmp_path / f"{CELL}.{SEED}.trace0.json").read_text())["record"]
    assert rec["in_window_compiles"] == 0 and rec["tokens_in_window"] > 0


def test_fp8_control_is_not_correct():
    import time

    cell, cfg, _ = small_model()
    params = serving.make_weights(cfg, SEED)
    eng = serving.make_engine(cfg, params, cell.config["serve"])
    serving.warm_up(eng, cell.traffic, cell.config["serve"])
    reqs = serving.schedule(cell.traffic, cfg.vocab, SEED, 1.0, False)
    t0 = time.perf_counter()
    serving.OpenLoop(eng, reqs, t0).run(t0 + 1.5)
    serving.release(eng, reqs)
    done = [r for r in reqs if r.finished]
    assert sum(len(r.tokens) for r in done) >= 200
    got = serving.readings(cell, params, done, cell.config["controls"])
    limit = cell.config["limits"]["logit_gap"]
    assert got["logit_gap"] <= limit < got["fp8"], got


def rebuilt(change_cfg=lambda c: c, change_params=lambda p: p):
    """A hook that serves another model in the engine's place: its config
    and weights changed, its programs traced anew."""
    def hook(eng, params):
        cfg = change_cfg(eng.cfg)
        eng.cfg, eng.params = cfg, change_params(eng.params)
        eng._states = M.init_slot_states(cfg, eng.scfg.batch_slots, eng.scfg.max_len)
        eng._decode = jax.jit(partial(M.prefill, cfg))
        eng._dispatch_greedy = jax.jit(partial(M.decode_slots_greedy, cfg))
    return hook


def drop_expert(params, e=1):
    p = jax.tree_util.tree_map(lambda x: x, params)
    for stack in p["periods"]:
        stack["ffn"]["wd"] = stack["ffn"]["wd"].at[:, e].set(0)
    return p


def unnormalized(logits, k):
    top, experts = jax.lax.top_k(logits, k)
    return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), experts, axis=-1), experts


FAULTS = {
    # every slot of a sliding layer's cache a full-length ring: no window
    "window_ignored": rebuilt(lambda c: dataclasses.replace(c, window=256)),
    "plain_rope_full_layers": rebuilt(lambda c: dataclasses.replace(c, yarn=None)),
    "held_expert_left_out": rebuilt(change_params=drop_expert),
    "gates_not_renormalized": rebuilt(),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    if fault == "gates_not_renormalized":
        monkeypatch.setattr(L, "route", unnormalized)
    r = run(small_cell(), engine_hook=FAULTS[fault])
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def synthetic_trace(lo_ns, hi_ns, steps):
    """One prefill, then the decode module once per decode step in the
    first half of the traced round."""
    mid = (lo_ns + hi_ns) / 2
    width = (mid - lo_ns - 4e6) / steps
    runs = [Event("jit__unknown(9)", lo_ns, lo_ns + 4e6)]
    runs += [Event("jit__unknown(7)", lo_ns + 4e6 + k * width, lo_ns + 4e6 + (k + 1) * width)
             for k in range(steps)]
    ops = [Event("%while.3 = bf16[8] while()", e.start, e.end) for e in runs]
    spans_ = [Event("window", lo_ns, hi_ns), Event("dispatch:step", lo_ns, hi_ns)]
    return Trace({"/device:TPU:0": ops}, spans_, {"/device:TPU:0": runs})


def test_traced_run_reports_the_per_layer_metrics(monkeypatch, tmp_path):
    import time

    bounds = {}
    real_run = serving.OpenLoop.run

    def run_and_note(self, until):
        bounds.setdefault("lo", time.perf_counter_ns())
        real_run(self, until)
        bounds["hi"] = time.perf_counter_ns()
        bounds["steps"] = sum(s.start * 1e9 >= bounds["lo"] and bool(s.context)
                              for s in self.steps)

    def traced(loop, start, length):
        bounds.clear()
        monkeypatch.setattr(serving.OpenLoop, "run", run_and_note)
        try:
            return real_traced(loop, start, length)
        finally:
            monkeypatch.setattr(serving.OpenLoop, "run", real_run)

    real_traced = serving.traced_round
    monkeypatch.setattr(serving, "traced_round", traced)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "unused")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda path: synthetic_trace(
        bounds["lo"], bounds["hi"], bounds["steps"]))
    r = run(small_cell(), trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == PER_LAYER
    assert 0 < r["metrics"]["serve_mfu.moe"]["value"]
    rec = json.loads((tmp_path / f"{CELL}.{SEED}.trace1.json").read_text())["record"]
    assert rec["traced"]["decode_module"][0] == "jit__unknown(7)"


def span(name, **attrs):
    return spans.Span(0, name, None, 0, 1, attrs)


def test_expert_counts_on_hand_built_spans(monkeypatch):
    m = {"n_layers": 4, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "d_head": 2, "d_ff": 16,
         "vocab": 32, "window": 4, "layer_types": ["sliding", "sliding", "sliding", "full"],
         "dtype": "bfloat16"}
    assert expert_counts.expert_flops(m, 3) == 3 * 3 * 2 * 8 * 16
    proj = 8 * 4 * 2 * 2 + 8 * 2 * 2 * 2
    # position 9: three sliding layers see 4 keys, the full one 10
    assert expert_counts.token_flops(m, 9) == 2 * (4 * proj + 8 * 32) + 4 * 4 * 2 * (3 * 4 + 10)
    recs = [span("serve.decode", pairs=1, experts=1, lens=[1]),
            span("serve.prefill", tokens=3, pairs=6, experts=2),
            span("serve.decode", pairs=2, experts=1, lens=[3, 9]),
            span("serve.decode", pairs=4, experts=2, lens=[4, 10])]
    for k, s in enumerate(recs):
        s.start_ns = k
    monkeypatch.setattr(spans, "records", lambda: recs)
    rec = {"model": m, "peak": {"flops_per_s": 1e30}, "traced": {"decode_steps": 2},
           "trace": {"busy_s": 2.0}}
    decode, prefill = expert_counts.round_spans(rec)
    assert [s.attrs["pairs"] for s in decode] == [2, 4] and prefill == []
    flops = expert_counts.round_flops(m, decode, prefill)
    assert flops == (sum(expert_counts.token_flops(m, n) for n in (3, 9, 4, 10))
                     + expert_counts.expert_flops(m, 6))
    mfu = harness.load_module(harness.ROOT / "bench" / "metrics" / "serve_mfu.moe.py",
                              "t_mfu").read
    assert mfu(rec) == pytest.approx(100 * flops / 2.0 / 1e30)
    assert mfu(dict(rec, traced={"decode_steps": 9})) is None
    monkeypatch.setattr(spans, "records", lambda: [])
    assert mfu(rec) is None
