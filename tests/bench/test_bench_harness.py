"""The benchmark harness: refusal off a chip, discovery of new files, and
the window's arithmetic on a fake clock."""
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "polybench-xl.b", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_host_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU" in r.stderr


def test_run_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("out", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_added_config_traffic_and_metric_are_found_without_edits(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "polybench-xl.json").read_text())
    cfg["name"] = "gemm-only"
    cfg["programs"] = {"gemm": cfg["programs"]["gemm"]}
    (b / "configs" / "gemm-only.json").write_text(json.dumps(cfg))
    shutil.copy(b / "references" / "polybench-xl.py", b / "references" / "gemm-only.py")
    (b / "traffic" / "burst.json").write_text(json.dumps(
        {"variant": "np", "mode": "equal_slices", "round_s": 7.0}))
    (b / "metrics" / "calls_total.py").write_text(
        "def read(rec):\n    return sum(p['calls'] for p in rec['programs'])\n")
    spec["configs"].append({"name": "gemm-only", "source": "test",
                            "file": "bench/configs/gemm-only.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "gemm-only.burst", "config": "gemm-only",
                              "traffic": "burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "calls_total", "unit": "calls", "better": "higher",
                              "source": "host_clock", "layer": "device",
                              "moves": "run_ms_geomean", "workloads": ["gemm-only.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("gemm-only.burst", root=tmp_path)
    assert cell.traffic["round_s"] == 7.0 and cell.traffic["variant"] == "np"
    assert list(cell.config["programs"]) == ["gemm"]
    assert cell.readers["calls_total"]({"programs": [{"calls": 2}, {"calls": 3}]}) == 5
    progs = harness.build_programs(cell.config, cell.traffic)
    assert progs[0].program.name == "gemm_np"
    # the metric that names its cell stays out of the others
    other = harness.load_cell("polybench-xl.b", root=tmp_path)
    assert "calls_total" not in other.readers
    assert {m["name"] for m in other.per_layer} == {
        "pass_pipeline_s", "db_recipe_share", "roofline_share", "idle_share", "mfu"}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _calls(clock, costs):
    def make(i, c):
        def call():
            clock.t += c
            return {"out": i}
        return call
    return [make(i, c) for i, c in enumerate(costs)]


def test_equal_slices_on_a_fake_clock():
    clock = FakeClock()
    costs = [0.1, 0.5, 0.05]
    tallies = [harness.Tally() for _ in costs]
    keep = {2: [None]}
    traffic = {"mode": "equal_slices"}
    for r in range(2):
        harness.run_round(_calls(clock, costs), ["a", "b", "c"], traffic, 1.5, costs,
                          tallies, clock=clock, start=r, keep=keep if r == 1 else None)
    assert [t.calls for t in tallies] == [10, 2, 20]
    assert [t.seconds for t in tallies] == [pytest.approx(1.0)] * 3
    assert [t.per_call_s for t in tallies] == [pytest.approx(c) for c in costs]
    assert keep[2][0] == {"out": 2}
    rec = {"programs": [{"seconds": t.seconds, "calls": t.calls} for t in tallies]}
    geo = cell_reader("run_ms_geomean")(rec)
    assert geo == pytest.approx(1e3 * (0.1 * 0.5 * 0.05) ** (1 / 3))


@pytest.mark.parametrize("seconds, round_s, want", [
    (51.0, 3.0, (17, 3.0)), (30.0, 3.0, (10, 3.0)), (10.0, 3.0, (3, 10 / 3)), (1.0, 3.0, (1, 1.0))])
def test_window_rounds_divide_the_window_evenly(seconds, round_s, want):
    n, length = harness.window_rounds(seconds, {"round_s": round_s})
    assert n == want[0] and length == pytest.approx(want[1])
    assert n * length == pytest.approx(seconds)


def test_configured_precision_is_applied():
    import jax

    before = jax.config.jax_default_matmul_precision
    try:
        harness.use_precision({"matmul_precision": "float32"})
        assert jax.config.jax_default_matmul_precision == "float32"
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def test_a_slice_holds_at_least_one_call():
    assert harness.slice_calls(0.1, 5.0) == 1
    assert harness.slice_calls(1.0, 0.3) == 3


def test_steps_on_a_fake_clock():
    clock = FakeClock()
    costs = [0.1, 0.2]
    tallies = [harness.Tally() for _ in costs]
    harness.run_round(_calls(clock, costs), ["a", "b"], {"mode": "steps"}, 1.0, costs,
                      tallies, clock=clock)
    # four steps of 0.3 s: the fourth ends past the round
    assert [t.calls for t in tallies] == [4, 4]
    assert [t.per_call_s for t in tallies] == [pytest.approx(0.1), pytest.approx(0.2)]


def test_unknown_traffic_mode_is_refused():
    with pytest.raises(ValueError, match="traffic mode"):
        harness.run_round([], [], {"mode": "poisson"}, 1.0, [], [])


def cell_reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py", f"t_{name}").read


def test_metric_readers_on_a_record():
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    rec = {"peak": peak, "window_s": 2.0, "setup_s": 9.5,
           "trace": {"busy_s": 0.75, "window_s": 1.0},
           "programs": [
               {"calls": 10, "seconds": 1.0, "flops": 1e10, "bytes": 1e9,
                "sources": ["exact", "default(blas3)"], "pass_s": 0.25},
               {"calls": 100, "seconds": 1.0, "flops": 1e8, "bytes": 1e9,
                "sources": ["transfer(d=1.5)", "default(recurrence)"], "pass_s": 0.5}]}
    assert cell_reader("setup_s")(rec) == 9.5
    assert cell_reader("pass_pipeline_s")(rec) == 0.75
    assert cell_reader("db_recipe_share")(rec) == 50.0
    assert cell_reader("idle_share")(rec) == pytest.approx(25.0)
    # least times: 10 * max(0.01, 0.01) + 100 * max(1e-4, 0.01) = 1.1 s of 2 s
    assert cell_reader("roofline_share")(rec) == pytest.approx(55.0)
    assert cell_reader("mfu")(rec) == pytest.approx(100 * (1e11 + 1e10) / 2.0 / 1e12)
    assert cell_reader("idle_share")(dict(rec, trace=None)) is None
    rec["programs"][0]["pass_s"] = None
    assert cell_reader("pass_pipeline_s")(rec) is None


def test_widest_gap():
    import numpy as np

    ref = np.array([1.0, -4.0, 2.0])
    assert harness.widest_gap(ref + [0, 0, 0.4], ref) == pytest.approx(0.1)
    assert harness.widest_gap(np.array([1.0, np.nan, 2.0]), ref) == math.inf
    assert harness.widest_gap(ref[:2], ref) == math.inf


def test_seed_of_any_size_gives_its_own_key():
    import numpy as np

    a = np.asarray(harness.prng_key(5))
    b = np.asarray(harness.prng_key(5 + (1 << 32)))
    assert not (a == b).all()


_CACHE_PROBE = """
import collections, sys
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp
from bench import harness
seen = collections.Counter()
jax.monitoring.register_event_listener(lambda e, **k: seen.update([e]))
harness.CACHE_DIR = Path(sys.argv[2])
harness.use_compile_cache()
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones(8)))
print(seen["/jax/compilation_cache/cache_hits"])
"""


def test_compile_cache_hits_past_an_entry_without_access_time(tmp_path):
    """A size limit in the environment turns on JAX's eviction, which reads
    every entry's access-time file before a write; an entry written with no
    limit has none.  The benchmark's cache must still be written and hit."""
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "jit_stale-0123-cache").write_bytes(b"written with no size limit")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_MAX_SIZE=str(10**9))
    hits = [subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(ROOT), str(cache)],
                           env=env, capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    first, second = (int(h.stdout.split()[-1]) for h in hits)
    assert first == 0 and second > 0, hits[-1].stderr[-2000:]
