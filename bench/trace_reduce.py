"""Reduce a profiler trace of the traced window to device busy time, the
device ops that took most time, and the idle gaps by host span.

The reduction works on plain intervals, so it can be checked on a
synthetic trace; ``read_xplane`` turns the ``.xplane.pb`` file that
``jax.profiler`` writes into those intervals.

* Device ops are the events of the ``XLA Ops`` line of every device plane.
  Busy time is the length of the union of their intervals inside the
  window, so ops that overlap count once; it is averaged over the devices.
  An op's time counts toward its name only where no earlier op encloses it
  (a ``while`` op encloses the ops of its body), so the ranked times add up
  to the busy time.  An op is named ``<program>:<HLO name>``: the program
  whose slice (``slice:<program>``) it ran in, and the HLO instruction's
  name without its shape and operands.
  An op that no slice encloses (a serving cell has none) is named by the
  module run it ran in instead, ``<module>(<program id>):<HLO name>``.
  The same ranking over every op, the enclosed ones too, names what runs
  inside the top-level loops (``nested_ops``).
* Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s
  (``window``, ``slice:<program>``, ``dispatch:<program>``, ``block``,
  ``inputs``).  Each idle gap of a device is charged to the ``dispatch:``,
  ``block`` or ``inputs`` span that overlaps it most.
* Modules are the events of the ``XLA Modules`` line: one per run of a
  compiled program, named ``<module>(<program id>)``.  The runs that lie
  whole inside the window are summed by that name.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Iterable, NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
SPAN_PREFIXES = ("dispatch:", "block", "inputs")
SLICE_PREFIX = "slice:"
NO_SPAN = "(no span)"


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns


class Trace(NamedTuple):
    devices: dict[str, list[Event]]  # device plane name -> its ops
    spans: list[Event]  # the benchmark's host spans
    modules: dict[str, list[Event]] = {}  # device plane name -> its module runs


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that the disjoint, sorted ``busy`` leaves."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


class Spans:
    """Host spans that do not overlap one another, sorted for lookup."""

    def __init__(self, spans: list[Event]):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]

    def at(self, lo: float, hi: float) -> str:
        """The span that overlaps ``[lo, hi]`` most, or ``NO_SPAN``."""
        best, best_len = NO_SPAN, 0.0
        k = max(0, bisect.bisect_right(self.starts, lo) - 1)
        while k < len(self.spans) and self.spans[k].start < hi:
            sp = self.spans[k]
            ov = min(hi, sp.end) - max(lo, sp.start)
            if ov > best_len:
                best, best_len = sp.name, ov
            k += 1
        return best


def short_name(op: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


def top_level(ops: list[Event]) -> list[Event]:
    """The ops that no earlier op encloses, by start time."""
    out: list[Event] = []
    end = float("-inf")
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        if o.start >= end:
            out.append(o)
            end = o.end
        elif o.end > end:  # overlaps without being enclosed: count the rest
            out.append(Event(o.name, end, o.end))
            end = o.end
    return out


def reduce_trace(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds, the ``top`` device ops by summed time, the
    ``top`` host spans by the idle time charged to them, the ``top`` ops by
    summed time counting enclosed ones too, and per module the seconds and
    count of its runs inside the window (``[name, s, runs]``, longest
    first).

    The window is the host span named ``window``; without one it is the
    extent of all device ops.
    """
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    all_ops = [op for ops in trace.devices.values() for op in ops]
    if not all_ops:
        raise ValueError("the trace holds no device op")
    if wins:
        lo, hi = min(w.start for w in wins), max(w.end for w in wins)
    else:
        lo, hi = min(o.start for o in all_ops), max(o.end for o in all_ops)
    spans = Spans([s for s in trace.spans if s.name.startswith(SPAN_PREFIXES)])
    slices = Spans([s for s in trace.spans if s.name.startswith(SLICE_PREFIX)])
    op_time: dict[str, float] = defaultdict(float)
    nested: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    mod_time: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    busy_total = 0.0
    for dev, ops in trace.devices.items():
        runs = trace.modules.get(dev, [])
        for r in runs:
            if r.start >= lo and r.end <= hi:
                t = mod_time[r.name]
                t[0] += r.end - r.start
                t[1] += 1
        modules = Spans(runs)
        inside = [Event(o.name, max(o.start, lo), min(o.end, hi)) for o in ops
                  if min(o.end, hi) > max(o.start, lo)]

        def named(o: Event) -> str:
            where = slices.at(o.start, o.end)
            if where != NO_SPAN:
                return f"{where[len(SLICE_PREFIX):]}:{short_name(o.name)}"
            run = modules.at(o.start, o.end)
            return f"{run if run != NO_SPAN else '?'}:{short_name(o.name)}"

        for o in top_level(inside):
            op_time[named(o)] += o.end - o.start
        for o in inside:
            nested[named(o)] += o.end - o.start
        busy = union((o.start, o.end) for o in inside)
        busy_total += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            idle[spans.at(*g)] += g[1] - g[0]
    n = len(trace.devices)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in rank(op_time)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in rank(idle)],
        "nested_ops": [[k, v / n / 1e9] for k, v in rank(nested)],
        "modules": [[k, s / n / 1e9, c / n] for k, (s, c) in
                    sorted(mod_time.items(), key=lambda kv: -kv[1][0])],
    }


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> Trace:
    """Device ops and the benchmark's host spans of one profiler trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = devices if line.name == OPS_LINE else modules
                    into[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name == WINDOW_SPAN
                          or e.name.startswith(SPAN_PREFIXES + (SLICE_PREFIX,))]
    devices = {k: v for k, v in devices.items() if v}
    return Trace(devices, spans, {k: v for k, v in modules.items() if k in devices})
