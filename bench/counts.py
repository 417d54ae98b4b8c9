"""Work counts of a source program: floating-point operations and the
compulsory bytes of one call, taken from its loop domains.

The counts are properties of the source program, not of any lowering, so
every variant of one PolyBench kernel (A, B, np) carries the same work,
whatever the compiler makes of it.  Both are lower bounds on what any
implementation of the callable must do, so a share of the chip's peak
built from them stays at or below 100%:

* operations: every arithmetic node of a computation's expression, counted
  once per point of its guarded iteration domain (a subtree repeated inside
  one expression counts once, as any compiler evaluates it once).  In an
  accumulating computation the constant factors of a top-level product are
  left out, since they can be applied once per output instead of once per
  term.  An opaque call counts the operations its configuration states.
* bytes: each array whose initial content the program reads is read once,
  and each array it writes is written once (the callable donates nothing,
  so every array it writes comes back as a fresh buffer).  Scalars (0-d
  arrays) are registers and count nothing.
"""
from __future__ import annotations

import math
from typing import Mapping

import numpy as np

from repro.core.ir import BinOp, Call, Const, Neg, Read, walk

ITEMSIZE = {"float32": 4, "float64": 8, "bfloat16": 2, "float16": 2}
# the largest grid of guard iterators enumerated to count a guarded domain
MAX_GUARD_POINTS = 1 << 27


def _loop_values(loop) -> np.ndarray:
    return loop.start + loop.step * np.arange(loop.trip_count, dtype=np.int64)


def domain_points(loops, guards) -> int:
    """Points of the box spanned by ``loops`` on which every affine guard
    is >= 0.  Iterators no guard names multiply the count by their trips;
    those that a guard names are enumerated together."""
    trips = {lp.iterator: lp.trip_count for lp in loops}
    if any(t <= 0 for t in trips.values()):
        return 0
    named = sorted({it for g in guards for it in g.iterators()})
    free = math.prod(t for it, t in trips.items() if it not in named)
    if not named:
        return free
    missing = [it for it in named if it not in trips]
    if missing:
        raise ValueError(f"guard names iterators {missing} outside its loops")
    if math.prod(trips[it] for it in named) > MAX_GUARD_POINTS:
        raise ValueError(f"guarded domain over {named} too large to enumerate")
    by_it = {lp.iterator: lp for lp in loops}
    grids = np.ix_(*[_loop_values(by_it[it]) for it in named])
    value = dict(zip(named, grids))
    mask = np.ones([trips[it] for it in named], dtype=bool)
    for g in guards:
        mask &= (g.const + sum(c * value[it] for it, c in g.coeffs)) >= 0
    return free * int(mask.sum())


def _factors(e) -> list:
    """The factors of a product tree (``a * b * c``, ``x / const``)."""
    if isinstance(e, BinOp) and e.op == "mul":
        return _factors(e.lhs) + _factors(e.rhs)
    if isinstance(e, BinOp) and e.op == "div" and isinstance(e.rhs, Const):
        return _factors(e.lhs) + [e.rhs]
    return [e]


def expr_ops(e, call_flops: Mapping[str, int], seen: set | None = None) -> int:
    """Arithmetic nodes of ``e``, each distinct subtree once."""
    seen = set() if seen is None else seen
    if isinstance(e, (Read, Const)) or e in seen:
        return 0
    seen.add(e)
    if isinstance(e, BinOp):
        return 1 + expr_ops(e.lhs, call_flops, seen) + expr_ops(e.rhs, call_flops, seen)
    if isinstance(e, Neg):
        return 1 + expr_ops(e.arg, call_flops, seen)
    if isinstance(e, Call):
        if e.fn_name not in call_flops:
            raise KeyError(f"no operation count for call {e.fn_name!r}; the "
                           f"configuration's call_flops has to state one")
        return int(call_flops[e.fn_name]) + sum(
            expr_ops(a, call_flops, seen) for a in e.args)
    raise TypeError(f"cannot count {type(e).__name__} nodes")


def comp_ops(comp, call_flops: Mapping[str, int]) -> int:
    """Operations of one evaluation of a computation (accumulation included)."""
    if comp.accumulate is None:
        return expr_ops(comp.expr, call_flops)
    factors = [f for f in _factors(comp.expr) if not isinstance(f, Const)]
    seen: set = set()
    ops = sum(expr_ops(f, call_flops, seen) for f in factors)
    return ops + max(0, len(factors) - 1) + 1


def program_flops(program, call_flops: Mapping[str, int] | None = None) -> int:
    """Floating-point operations of one call of ``program``."""
    call_flops = call_flops or {}
    total = 0
    for body in program.body:
        for loops, comp in walk(body):
            total += domain_points(loops, comp.guards) * comp_ops(comp, call_flops)
    return total


def _full_cover(loops, comp, shape) -> bool:
    """Whether a plain write of ``comp`` sets every element of its array."""
    if comp.accumulate is not None or comp.guards or len(comp.write.index) != len(shape):
        return False
    by_it = {lp.iterator: lp for lp in loops}
    its = []
    for ix, dim in zip(comp.write.index, shape):
        names = ix.iterators()
        if len(names) != 1 or ix.const != 0 or ix.coeff(names[0]) != 1:
            return False
        lp = by_it.get(names[0])
        if lp is None or lp.start != 0 or lp.step != 1 or lp.trip_count != dim:
            return False
        its.append(names[0])
    return len(set(its)) == len(its)


def program_bytes(program) -> int:
    """Compulsory bytes of one call: inputs read once, outputs written once."""
    arrays = {a.name: a for a in program.arrays}
    temps = set(program.temps)
    seen: set[str] = set()
    needed: set[str] = set()
    written: set[str] = set()
    for body in program.body:
        for loops, comp in walk(body):
            for r in comp.reads:
                if r.array not in seen:
                    seen.add(r.array)
                    needed.add(r.array)
            w = comp.write.array
            if w not in seen:
                seen.add(w)
                if not _full_cover(loops, comp, arrays[w].shape):
                    needed.add(w)
            written.add(w)
    total = 0
    for name, a in arrays.items():
        if not a.shape:
            continue
        nbytes = math.prod(a.shape) * ITEMSIZE[a.dtype]
        total += nbytes * ((name in needed and name not in temps) + (name in written))
    return total


def written_arrays(program) -> list[str]:
    """The non-scalar arrays ``program`` writes: what a call answers."""
    shapes = {a.name: a.shape for a in program.arrays}
    out: list[str] = []
    for body in program.body:
        for _, comp in walk(body):
            w = comp.write.array
            if shapes[w] and w not in out:
                out.append(w)
    return out


def least_seconds(flops: int, nbytes: int, peak: Mapping[str, float]) -> tuple[float, str]:
    """The least time one call can take on a chip with ``peak``, and which
    bound sets it (``"flops"`` or ``"bytes"``)."""
    t_f = flops / peak["flops_per_s"]
    t_b = nbytes / peak["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
