"""The trace reduction (``bench/trace_reduce.py``) on synthetic traces."""
import pytest

from bench.trace_reduce import Event, Trace, gaps, reduce_trace, short_name, top_level, union

S = 1e9  # ns per second


def test_union_counts_overlapping_ops_once():
    assert union([(0, 10), (5, 15), (20, 30), (30, 31)]) == [(0, 15), (20, 31)]


def test_gaps():
    assert gaps([(0, 10), (20, 30), (40, 50)], 0, 50) == [(10, 20), (30, 40)]
    assert gaps([], 0, 5) == [(0, 5)]


def test_enclosed_ops_do_not_count_twice():
    ops = [Event("while", 0, 10), Event("fusion", 2, 4), Event("copy", 8, 12),
           Event("dot", 20, 25)]
    assert top_level(ops) == [Event("while", 0, 10), Event("copy", 10, 12),
                              Event("dot", 20, 25)]


def test_short_name():
    assert short_name("%fusion.22 = f32[65536]{0} fusion(f32[2] %a), kind=kCustom") == "fusion.22"
    assert short_name("copy-start") == "copy-start"


def _trace():
    ops = [Event("%fusion.1 = f32[8] fusion()", 0.0 * S, 1.0 * S),
           Event("%dot.2 = f32[8] dot()", 0.5 * S, 2.0 * S),
           Event("%fusion.1 = f32[8] fusion()", 3.3 * S, 3.8 * S),
           Event("late", 9.0 * S, 11.0 * S)]
    spans = [Event("window", 0.0, 4.0 * S),
             Event("slice:gemm", 0.0, 3.2 * S), Event("slice:atax", 3.2 * S, 4.0 * S),
             Event("dispatch:gemm", 0.0, 2.4 * S),
             Event("block", 2.4 * S, 3.2 * S), Event("dispatch:atax", 3.2 * S, 4.0 * S),
             Event("unrelated", 0.0, 4.0 * S)]
    return Trace({"/device:TPU:0": ops}, spans)


def test_busy_time_and_window():
    r = reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(4.0)
    # [0, 2] and [3.3, 3.8] inside the window; the late op is outside it
    assert r["busy_s"] == pytest.approx(2.5)


def test_device_ops_ranked_by_summed_time():
    r = reduce_trace(_trace())
    # named by the slice each ran in; dot.2 counts only where fusion.1 does
    # not already cover it, so the ranked times add up to the busy time
    assert dict(r["device_ops"]) == {"gemm:fusion.1": pytest.approx(1.0),
                                     "gemm:dot.2": pytest.approx(1.0),
                                     "atax:fusion.1": pytest.approx(0.5)}


def test_idle_gap_goes_to_the_host_span_over_it():
    r = reduce_trace(_trace())
    idle = dict(r["idle_gaps"])
    # gap [2, 3.3] lies mostly under block (0.8 s of it); [3.8, 4] under dispatch:atax
    assert idle == {"block": pytest.approx(1.3), "dispatch:atax": pytest.approx(0.2)}
    assert "unrelated" not in idle


def test_gap_with_a_clear_span():
    ops = [Event("a", 0.0, 1.0 * S), Event("b", 3.0 * S, 4.0 * S)]
    spans = [Event("window", 0.0, 4.0 * S), Event("dispatch:x", 0.0, 1.1 * S),
             Event("block", 1.1 * S, 4.0 * S)]
    r = reduce_trace(Trace({"/device:TPU:0": ops}, spans))
    assert dict(r["idle_gaps"]) == {"block": pytest.approx(2.0)}


def test_busy_is_averaged_over_devices():
    spans = [Event("window", 0.0, 2.0 * S)]
    devs = {"/device:TPU:0": [Event("a", 0.0, 2.0 * S)],
            "/device:TPU:1": [Event("a", 0.0, 1.0 * S)]}
    r = reduce_trace(Trace(devs, spans))
    assert r["busy_s"] == pytest.approx(1.5)
    assert dict(r["idle_gaps"]) == {"(no span)": pytest.approx(0.5)}


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device op"):
        reduce_trace(Trace({}, [Event("window", 0.0, 1.0)]))


def test_module_runs_name_ops_outside_slices_and_are_summed_whole():
    ops = [Event("%while.3 = f32[8] while()", 1.0 * S, 3.0 * S),
           Event("%fusion.2 = f32[8] fusion()", 1.5 * S, 2.0 * S),
           Event("%fusion.9 = f32[8] fusion()", 3.5 * S, 3.9 * S)]
    runs = [Event("jit__unknown(11)", 0.9 * S, 3.1 * S),
            Event("jit__unknown(22)", 3.4 * S, 4.5 * S)]  # ends past the window
    spans = [Event("window", 0.0, 4.0 * S), Event("dispatch:step", 0.0, 4.0 * S)]
    r = reduce_trace(Trace({"/device:TPU:0": ops}, spans, {"/device:TPU:0": runs}))
    assert r["device_ops"] == [["jit__unknown(11):while.3", pytest.approx(2.0)],
                               ["jit__unknown(22):fusion.9", pytest.approx(0.4)]]
    assert ["jit__unknown(11):fusion.2", pytest.approx(0.5)] in r["nested_ops"]
    assert r["modules"] == [["jit__unknown(11)", pytest.approx(2.2), 1.0]]
    assert reduce_trace(_trace())["modules"] == []
