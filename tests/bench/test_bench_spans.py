"""The set-up metrics read from the program's compile-path spans
(``bench/program_spans.py`` and its five readers): on hand-built spans, on
a program without spans, and through a traced run at a small size on the
CPU."""
import sys
import time

import jax
import pytest

from bench import harness
from repro.core import spans
from repro.core.spans import Span
from repro.polybench import BENCHMARKS

SETUP_METRICS = ("daisy_compile_s", "codegen_s", "lower_s", "xla_compile_s",
                 "compile_cache_hit_share")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py", f"t_spans_{name}").read


def span(name, seconds, **attrs):
    return Span(0, name, None, 0, int(seconds * 1e9), attrs)


HAND_BUILT = [
    span("daisy.compile", 0.5, program="a", cached=False),
    span("daisy.pipeline", 0.25),
    span("daisy.compile", 0.25, program="a", cached=True),
    span("jax.trace", 1.0, module="daisy_a"),
    span("codegen.emit", 0.75, program="a"),
    span("jax.lower", 2.0, module="daisy_a"),
    span("xla.compile", 3.0, module="daisy_a", cache="hit"),
    span("xla.compile", 4.0, module="daisy_b", cache="miss"),
    span("xla.compile", 5.0, module="daisy_c", cache="hit"),
    span("xla.compile", 6.0, module="daisy_d", cache="off"),
]


@pytest.mark.parametrize("name, want", [
    ("daisy_compile_s", 0.75), ("codegen_s", 0.75), ("lower_s", 2.0),
    ("xla_compile_s", 18.0), ("compile_cache_hit_share", 100.0 * 2 / 3)])
def test_reader_on_hand_built_spans(monkeypatch, name, want):
    monkeypatch.setattr(spans, "records", lambda: list(HAND_BUILT))
    assert reader(name)({}) == pytest.approx(want)


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_reader_without_spans_reads_none(monkeypatch, name):
    monkeypatch.setattr(spans, "records", lambda: [])
    assert reader(name)({}) is None


def test_cache_share_without_a_cache_reads_none(monkeypatch):
    monkeypatch.setattr(spans, "records", lambda: [span("xla.compile", 1.0, cache="off")])
    assert reader("compile_cache_hit_share")({}) is None


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_reader_of_a_program_without_the_recorder_reads_none(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)  # import fails
    assert reader(name)({}) is None


@pytest.fixture
def compile_cache(tmp_path, request):
    """A persistent compile cache in ``tmp_path`` for one test, as the
    benchmark's entry point sets one; JAX's settings are restored after."""
    from jax._src import compilation_cache

    values = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in values}

    def restore():
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

    request.addfinalizer(restore)
    for k, v in values.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def small_cell(name: str, monkeypatch, tmp_path):
    """Cell ``name`` at the suite's mini sizes (PolyBench: gemm and atax),
    with the five metrics applied to it and the chip's peaks and trace
    stubbed."""
    config, traffic = name.split(".")
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in spec["per_layer"]:
        if m["name"] in SETUP_METRICS:
            m["workloads"] = sorted(set(m["workloads"]) | {name})
    cell = harness.make_cell(spec, name, config, f"bench/configs/{config}.json", traffic)
    progs = cell.config["programs"]
    for prog, e in list(progs.items()):
        if e["builder"] != "polybench":
            e["sizes"] = dict(e["sizes"], nproma=64)
        elif prog in ("gemm", "atax"):
            inv = {v: k for k, v in e["suite_keys"].items()}
            e["sizes"] = {inv[k]: v for k, v in BENCHMARKS[prog].sizes["mini"].items()}
        else:
            del progs[prog]
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"flops_per_s": 1e12,
                                                            "bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    # a CPU trace holds no device op to reduce; the reduction has tests of its own
    monkeypatch.setattr(harness, "traced_round", lambda *a: {
        "busy_s": 0.5, "window_s": 1.0, "device_ops": [], "idle_gaps": []})
    return cell


@pytest.mark.parametrize("name", ["cloudsc-l137.step", "polybench-xl.b"])
def test_traced_run_reports_the_setup_metrics(name, monkeypatch, tmp_path, compile_cache):
    cell = small_cell(name, monkeypatch, tmp_path)
    spans.reset()  # this process compiled other tests' programs before
    r = harness.run_cell(cell, (1 << 33) + 5, 0.15, True, time.perf_counter())
    assert r["correct"], r["checks"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(SETUP_METRICS) <= set(got)
    assert {r["metrics"][k]["unit"] for k in SETUP_METRICS} == {"s", "%"}
    assert all(got[k] > 0 for k in SETUP_METRICS[:4])
    assert got["compile_cache_hit_share"] == 0.0  # a fresh cache only misses
    record = harness.load_json(tmp_path / f"{name}.{(1 << 33) + 5}.trace1.json")["record"]
    assert sum(got[k] for k in SETUP_METRICS[:4]) <= record["setup_s"]
    assert {"db_recipe_share", "roofline_share", "mfu"} <= set(got)
