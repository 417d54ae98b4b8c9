"""The check that decides ``correct``, driven through a whole run at a small
size on the CPU: a sound run passes; the control (the reference computed in
bfloat16, in the program's place) and each fault the cells can have
(state returned unchanged, an answer altered where it is produced, half of
the columns left out) come out not correct.  The chip's look for a TPU is
skipped; everything after it runs."""
import time

import numpy as np
import pytest

from bench import calibrate, harness
from repro.polybench import BENCHMARKS

POLYBENCH_SUBSET = ("gemm", "atax", "jacobi-2d", "correlation")


def small_cell(name: str):
    config, traffic = name.split(".")
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.make_cell(spec, name, config, f"bench/configs/{config}.json", traffic)
    progs = cell.config["programs"]
    for prog, e in list(progs.items()):
        if e["builder"] == "polybench":
            if prog not in POLYBENCH_SUBSET:
                del progs[prog]
                continue
            inv = {v: k for k, v in e["suite_keys"].items()}
            e["sizes"] = {inv[k]: v for k, v in BENCHMARKS[prog].sizes["mini"].items()}
        else:
            e["sizes"] = dict(e["sizes"], nproma=64)
    return cell


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"flops_per_s": 1e12,
                                                            "bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)


def run(cell, compile_fn=harness.compile_programs, seed=(1 << 33) + 17):
    return harness.run_cell(cell, seed, 0.15, False, time.perf_counter(), compile_fn=compile_fn)


def broken(fault):
    """``compile_programs`` with each callable's outputs passed through ``fault``."""
    def compile_fn(progs):
        fns, plans, daisy = harness.compile_programs(progs)
        wrapped = [(lambda x, f=f, p=p: fault(p, x, f(x))) for f, p in zip(fns, progs)]
        return wrapped, plans, daisy
    return compile_fn


def unchanged(p, x, out):
    """A step that returns its state unchanged: every array as it came in
    (the program's own temporaries as it starts them, zeroed)."""
    return {k: x[k] if k in x else v * 0 for k, v in out.items()}


def altered(p, x, out):
    """One element of one answer off by a hundredth of the array's range."""
    out = dict(out)
    k = p.outputs[-1]
    a = out[k]
    out[k] = a.at[(0,) * a.ndim].add(0.01 * abs(a).max() + 1e-3)
    return out


def half_columns(p, x, out):
    """Half of the batch (the columns, axis 1 of every CLOUDSC field) left out."""
    out = dict(out)
    for k in p.outputs:
        a = out[k]
        cols = a.shape[1] // 2
        out[k] = a.at[:, cols:].set(0.0)
    return out


@pytest.mark.parametrize("cell", ["polybench-xl.b", "cloudsc-l137.step"])
def test_sound_run_is_correct(cell):
    r = run(small_cell(cell))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == len(r["checks"])
    assert set(r["metrics"]) == {"run_ms_geomean", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", ["polybench-xl.b", "cloudsc-l137.step"])
def test_bfloat16_control_is_not_correct(cell):
    c = small_cell(cell)
    r = run(c, calibrate.control_compile(c))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell, fault", [
    ("polybench-xl.b", unchanged),
    ("polybench-xl.b", altered),
    ("cloudsc-l137.step", unchanged),
    ("cloudsc-l137.step", altered),
    ("cloudsc-l137.step", half_columns),
])
def test_fault_is_not_correct(cell, fault):
    r = run(small_cell(cell), broken(fault))
    assert not r["correct"]
    assert r["failed"] == len(r["checks"]) or fault is altered
    assert r["failed"] >= 1


def test_answers_checked_are_those_the_window_produced(monkeypatch):
    """The answer checked is the output of a call inside the window: a
    fault that strikes only there is caught."""
    in_window = {"on": False}
    real_round = harness.run_round

    def window_round(*args, **kwargs):
        in_window["on"] = True
        try:
            return real_round(*args, **kwargs)
        finally:
            in_window["on"] = False

    def window_only(p, x, out):
        return unchanged(p, x, out) if in_window["on"] else out

    monkeypatch.setattr(harness, "run_round", window_round)
    r = run(small_cell("cloudsc-l137.step"), broken(window_only))
    assert not r["correct"]


def test_readings_of_sound_program_sit_far_below_the_control():
    c = small_cell("cloudsc-l137.step")
    progs = harness.build_programs(c.config, c.traffic)
    fns, _, _ = harness.compile_programs(progs)
    sound = calibrate.readings(c, progs, fns, [5, 6])
    cfns, _, _ = calibrate.control_compile(c)(progs)
    control = calibrate.readings(c, progs, cfns, [5])
    for name in sound:
        assert max(sound[name]) * 3 <= min(control[name]), (name, sound, control)
        assert np.isfinite(sound[name]).all()
