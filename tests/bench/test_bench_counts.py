"""Work counts of the benchmark (``bench/counts.py``) and its peaks table."""
import json
from pathlib import Path

import pytest

from bench import counts, harness
from repro.polybench import BENCHMARKS

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "bench" / "configs" / "polybench-xl.json").read_text())


def _sizes(name: str, size: str) -> dict:
    entry = CONFIG["programs"][name]
    if size == "config":
        return harness.polybench_sizes(BENCHMARKS[name], entry["sizes"], entry["suite_keys"])
    return BENCHMARKS[name].sizes[size]


@pytest.mark.parametrize("size", ["mini", "config"])
@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_variants_count_the_same_work(name, size):
    bench = BENCHMARKS[name]
    s = _sizes(name, size)
    got = {v: (counts.program_flops(f(s), CONFIG["call_flops"]), counts.program_bytes(f(s)))
           for v, f in bench.variants.items()}
    assert len(set(got.values())) == 1, got
    flops, nbytes = got["a"]
    assert flops > 0 and nbytes > 0


def test_gemm_matches_hand_formula():
    s = BENCHMARKS["gemm"].sizes["mini"]
    ni, nj, nk = s["ni"], s["nj"], s["nk"]
    prog = BENCHMARKS["gemm"].make("b", "mini")
    # C *= beta: one mul per element; C += alpha*A*B: alpha applied once per
    # output, so one mul and one add per term
    assert counts.program_flops(prog) == ni * nj + 2 * ni * nj * nk
    # A and B read; C read and written
    assert counts.program_bytes(prog) == 4 * (ni * nk + nk * nj + 2 * ni * nj)


def test_atax_matches_hand_formula():
    s = BENCHMARKS["atax"].sizes["mini"]
    m, n = s["m"], s["n"]
    prog = BENCHMARKS["atax"].make("a", "mini")
    assert counts.program_flops(prog) == 2 * m * n + 2 * m * n
    # A and x read; y and tmp are zeroed before use, so only written
    assert counts.program_bytes(prog) == 4 * (m * n + n + n + m)


def test_guarded_domain_counts_the_triangle():
    s = BENCHMARKS["syrk"].sizes["mini"]
    n, m = s["n"], s["m"]
    prog = BENCHMARKS["syrk"].make("b", "mini")
    tri = n * (n + 1) // 2
    assert counts.program_flops(prog) == tri + 2 * tri * m


def test_opaque_call_needs_a_stated_count():
    prog = BENCHMARKS["correlation"].make("a", "mini")
    with pytest.raises(KeyError, match="finish_std"):
        counts.program_flops(prog, {})


def test_least_seconds_names_its_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 10, peak) == (10.0, "flops")
    assert counts.least_seconds(10, 1000, peak) == (100.0, "bytes")


def test_peaks_table_knows_v5e_and_refuses_an_unknown_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")
