"""The serving run (``bench/serving.py``): a whole run at a small size on
the CPU through the harness, the seeded schedule, the metric readers on a
synthetic record, the traced round on a synthetic trace, and discovery of
a serving configuration added as files."""
import json
import shutil

import numpy as np
import pytest
from serving_cell import CELL, SEED, run, small_cell

from bench import harness, serving, trace_reduce
from bench.trace_reduce import Event, Trace

ROOT = harness.ROOT
E2E = {"setup_s", "itl_p50_ms", "itl_p99_ms", "tokens_per_s"}
PER_LAYER = {"serve_mfu", "decode_roofline", "idle_share.serve", "batch_occupancy",
             "queue_ms_p50"}


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"flops_per_s": 1e12,
                                                            "bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)


def table(tmp_path, trace=False):
    return json.loads((tmp_path / f"{CELL}.{SEED}.trace{int(trace)}.json").read_text())


def test_run_through_the_harness_is_correct_and_compiles_nothing_in_the_window(tmp_path):
    import time

    cell = small_cell()
    r = harness.run_cell(cell, SEED, 1.0, False, time.perf_counter())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == E2E
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert r["attempted"] == 20 and r["failed"] == 0
    assert set(r["checks"]) == {"logit_gap", "failed_requests", "degradations"}
    rec = table(tmp_path)["record"]
    assert rec["in_window_compiles"] == 0
    assert rec["requests_due"] == 20 and rec["lateness_ms"]["max"] is not None
    assert rec["tokens_in_window"] > 0
    assert r["metrics"]["tokens_per_s"]["value"] == rec["tokens_in_window"] / 1.0


def synthetic_trace(lo_ns, hi_ns, steps):
    """A device that runs one prefill, then the decode module once per
    decode step, in the first half of the traced round and nothing in the
    second, but for a short eager update that runs more often than the
    decode module."""
    mid = (lo_ns + hi_ns) / 2
    width = (mid - lo_ns - 4e6) / steps
    runs = [Event("jit__unknown(9)", lo_ns, lo_ns + 4e6)]
    runs += [Event("jit__unknown(7)", lo_ns + 4e6 + k * width, lo_ns + 4e6 + (k + 1) * width)
             for k in range(steps)]
    ops = [Event("%fusion.1 = bf16[8] fusion()", e.start, e.end) for e in runs]
    runs += [Event("jit_convert_element_type(3)", mid + 10 * k, mid + 10 * k + 1)
             for k in range(steps + 2)]
    spans = [Event("window", lo_ns, hi_ns), Event("dispatch:step", lo_ns, hi_ns)]
    return Trace({"/device:TPU:0": ops}, spans, {"/device:TPU:0": runs})


def test_traced_run_reports_the_per_layer_metrics(monkeypatch, tmp_path):
    import time

    bounds = {}
    real_run = serving.OpenLoop.run

    def run_and_note(self, until):
        bounds.setdefault("lo", time.perf_counter_ns())
        real_run(self, until)
        bounds["hi"] = time.perf_counter_ns()
        bounds["steps"] = sum(s.start * 1e9 >= bounds["lo"] and bool(s.context) for s in self.steps)

    def fake_read(path):
        return synthetic_trace(bounds["lo"], bounds["hi"], bounds["steps"])

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
    monkeypatch.setattr(trace_reduce, "read_xplane", fake_read)
    r = run(small_cell(), trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == PER_LAYER
    assert r["metrics"]["idle_share.serve"]["value"] == pytest.approx(50.0, abs=1.0)
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert r["breakdown"]["device_ops"][0][0] == "jit__unknown(7):fusion.1"
    traced_rec = table(tmp_path, trace=True)["record"]["traced"]
    assert traced_rec["decode_module"][0] == "jit__unknown(7)"
    assert traced_rec["decode_steps"] > 0 and traced_rec["flops"] > 0


def test_same_seed_same_schedule_other_seed_same_lengths_in_another_order():
    traffic = small_cell().traffic
    a = serving.schedule(traffic, 512, 7, 3.0, True)
    b = serving.schedule(traffic, 512, 7, 3.0, True)
    c = serving.schedule(traffic, 512, (1 << 40) + 7, 3.0, True)
    key = [(r.phase, r.due, r.target, r.prompt.tolist()) for r in a]
    assert key == [(r.phase, r.due, r.target, r.prompt.tolist()) for r in b]
    assert key != [(r.phase, r.due, r.target, r.prompt.tolist()) for r in c]
    for phase, span in (("lead_in", 0.5), ("window", 3.0), ("trace", 0.5)):
        pa = [r for r in a if r.phase == phase]
        pc = [r for r in c if r.phase == phase]
        assert len(pa) == len(pc) == round(20.0 * span)
        assert sorted(r.target for r in pa) == sorted(r.target for r in pc)
        assert sorted(len(r.prompt) for r in pa) == sorted(len(r.prompt) for r in pc)
    due = [r.due for r in a]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 4.0
    assert all(8 <= len(r.prompt) <= 128 and 4 <= r.target <= 32 for r in a)


def test_arrivals_are_burstier_than_poisson():
    g = serving.gaps({"process": "gamma", "cv": 2.0}, 1.5, 400, 400 / 1.5)
    assert g.sum() == pytest.approx(400 / 1.5)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.15)


def test_lengths_follow_the_stated_quantiles():
    x = serving.stratified({"dist": "lognormal", "median": 512, "sigma": 0.9,
                            "min": 128, "max": 4096}, 101)
    assert x[50] == 512 and x.min() >= 128 and x.max() <= 4096
    assert list(x) == sorted(x)


def reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py", f"t_serve_{name}").read


def test_serving_metric_readers_on_a_record():
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    rec = {"itl_ms": [float(x) for x in range(1, 201)],
           "queue_ms": [5.0, 1.0, 3.0], "tokens_in_window": 510, "window_s": 51.0,
           "occupancy_pct": 62.5, "peak": peak, "setup_s": 40.0,
           "trace": {"busy_s": 2.0, "window_s": 4.0},
           "traced": {"flops": 5e11, "decode_least_s_mean": 0.004,
                      "decode_module": ["jit__unknown(7)", 1.0, 100]}}
    assert reader("itl_p50_ms")(rec) == 100.0
    assert reader("itl_p99_ms")(rec) == 198.0
    assert reader("tokens_per_s")(rec) == 10.0
    assert reader("queue_ms_p50")(rec) == 3.0
    assert reader("batch_occupancy")(rec) == 62.5
    # 100 runs of 0.004 s least time in 1.0 s of device time
    assert reader("decode_roofline")(rec) == pytest.approx(40.0)
    assert reader("serve_mfu")(rec) == pytest.approx(100 * 5e11 / 2.0 / 1e12)
    assert reader("idle_share.serve")(rec) == pytest.approx(50.0)
    rec["traced"]["decode_module"] = None
    assert reader("decode_roofline")(rec) is None
    assert reader("serve_mfu")(dict(rec, trace=None)) is None
    assert reader("queue_ms_p50")(dict(rec, queue_ms=[])) is None


def test_metric_sets_of_every_cell():
    sets = {}
    for name in ("polybench-xl.b", "cloudsc-l137.step", CELL):
        c = harness.load_cell(name)
        sets[name] = ({m["name"] for m in c.end_to_end}, {m["name"] for m in c.per_layer})
    compiler = {"pass_pipeline_s", "db_recipe_share", "roofline_share", "idle_share", "mfu"}
    setup = {"daisy_compile_s", "codegen_s", "lower_s", "xla_compile_s",
             "compile_cache_hit_share"}
    assert sets["polybench-xl.b"] == ({"run_ms_geomean", "setup_s"}, compiler)
    assert sets["cloudsc-l137.step"] == ({"run_ms_geomean", "setup_s"}, compiler | setup)
    assert sets[CELL] == (E2E, PER_LAYER)


def test_added_serving_config_and_traffic_are_found_without_edits(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "danube-3-4b.json").read_text())
    cfg.update(name="minicpm-like", arch="minicpm-2b")
    (b / "configs" / "minicpm-like.json").write_text(json.dumps(cfg))
    shutil.copy(b / "references" / "danube-3-4b.py", b / "references" / "minicpm-like.py")
    traffic = json.loads((b / "traffic" / "chat.json").read_text())
    (b / "traffic" / "agent.json").write_text(json.dumps(dict(traffic, rate_per_s=0.25)))
    spec["configs"].append({"name": "minicpm-like", "source": "test",
                            "file": "bench/configs/minicpm-like.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "minicpm-like.agent", "config": "minicpm-like",
                              "traffic": "agent", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("minicpm-like.agent")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("minicpm-like.agent", root=tmp_path)
    assert cell.config["arch"] == "minicpm-2b" and cell.traffic["rate_per_s"] == 0.25
    assert {m["name"] for m in cell.end_to_end} == E2E
    assert {m["name"] for m in cell.per_layer} == PER_LAYER
    assert serving.model_config(cell.config).name == "minicpm-2b"
    reqs = serving.schedule(cell.traffic, cell.config["model"]["vocab"], 1, 51.0, False)
    assert sum(r.phase == "window" for r in reqs) == round(0.25 * 51)


def test_a_builder_without_its_module_is_refused(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    cfg = json.loads((tmp_path / "bench" / "configs" / "danube-3-4b.json").read_text())
    cfg["builder"] = "training"
    (tmp_path / "bench" / "configs" / "danube-3-4b.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(FileNotFoundError, match="bench/training.py"):
        harness.load_cell(CELL, root=tmp_path)


def test_nearest_rank():
    from bench.serve_counts import nearest_rank

    assert nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
    assert nearest_rank(list(range(1, 11)), 90) == 9
    assert nearest_rank(list(range(1, 11)), 100) == 10
    assert nearest_rank([], 90) is None


def test_decode_least_time_counts_weights_and_live_cache():
    from bench import serve_counts as sc

    m = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "d_head": 2,
         "d_ff": 16, "vocab": 32, "window": None, "dtype": "bfloat16"}
    per_layer = 8 * 8 * 2 + 8 * 4 * 2 + 3 * 8 * 16
    assert sc.matmul_params(m) == 2 * per_layer + 8 * 32
    assert sc.kv_bytes_per_token(m) == 2 * 2 * 2 * 2 * 2
    peak = {"bytes_per_s": 1.0, "flops_per_s": 1e30}
    weights = (sc.matmul_params(m) + 5 * 8 + 2 * 8) * 2
    assert sc.decode_least_seconds(m, [3, 9], peak) == weights + 32 * (4 + 10)
    assert sc.prefill_flops(m, 3) == 2 * sc.matmul_params(m) * 3 + 4 * 2 * 4 * 2 * 6
    assert sc.prefill_flops(dict(m, window=2), 3) == (2 * sc.matmul_params(m) * 3
                                                      + 4 * 2 * 4 * 2 * 5)
    assert np.isclose(sc.token_flops(m, 9), 2 * sc.matmul_params(m) + 4 * 2 * 4 * 2 * 10)
