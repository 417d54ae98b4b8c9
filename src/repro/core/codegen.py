"""Code generation: loop-nest IR -> executable code.

Three lowerings:

* ``execute_numpy``  — the semantic oracle: literal nested Python loops over
  numpy arrays.  Slow; used by tests to validate every other path.
* ``compile_jax(mode='as_written')`` — the *baseline compiler* analogue: the
  nest is lowered in its authored loop order; only each computation's
  innermost legal loop is vectorized (what ``clang -O3``'s auto-vectorizer
  sees), everything else becomes sequential ``lax.fori_loop``s.  No idioms.
* ``compile_jax(mode='canonical')`` — the scheduled path: every legal
  iterator is vectorized (subject to a materialization budget), reductions
  become vector reductions, and BLAS-class computations are dispatched to
  ``jnp.einsum`` / Pallas (idiom detection), mirroring the paper's recipe DB.

On top of the canonical path, two recipe-selected lowerings:

* ``Schedule.pallas_nest`` / ``Schedule.pallas_reduce`` route whole canonical
  nests through the grid-tiled Pallas kernel (``repro.core.tiling`` plans the
  grid, ``repro.kernels.nest_kernel`` emits the ``pallas_call``); nests
  outside the tiled class fall back to the generic path silently.
* ``Schedule.scan`` lowers carried (recurrence) loops to ``lax.scan`` with
  leading-axis operands sliced per step instead of whole arrays carried
  through a ``fori_loop`` and re-gathered every iteration.

Legality is decided with the same dependence machinery the normalizer uses:
an iterator may be materialized as an array axis iff no dependence of the
nest is carried by it (reduction self-deps of flagged accumulations exempt).
In canonical mode the plan also drops access pairs that the loop bounds and
guards prove disjoint (``dependence.guarded_disjoint``), so correlation's
triangular ``corr[k6,k5] = corr[k5,k6] if k6 > k5`` becomes one masked
transpose rather than a loop over single elements.

Names on the device: the function ``compile_jax`` builds is named after the
program (``daisy_<program>``, so its XLA module is ``jit_daisy_<program>``);
top-level nest ``i`` is emitted under ``jax.named_scope(f"nest{i}")`` and
each lowering the emitter picks under a scope of its own name (``einsum``,
``vectorize``, ``scan``, ``fori``, ``pallas_nest``, ``pallas_reduce``,
``pallas_gemm``), so a device op's ``op_name`` reads like
``jit(daisy_heat_3d)/nest0/fori/...``.  Emission is recorded as a
``codegen.emit`` span with one ``codegen.nest`` span per top-level nest
(``repro.core.spans``); it runs only while JAX traces.
"""
from __future__ import annotations

import itertools
import math
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from dataclasses import replace as dc_replace
from typing import Any, Callable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..device import pallas_backend
from .dependence import EQ, nest_direction_vectors
from .ir import (
    Access,
    Affine,
    Array,
    Computation,
    Loop,
    Node,
    Program,
    loop_iterators,
    walk,
)
from .spans import module_name, span

# Shared accumulate-op semantics: neutral elements and reducers.  The Pallas
# nest kernel (repro.kernels.nest_kernel) imports these (plus ``_combine``)
# so both lowerings stay in sync when an accumulate op is added.
_ACC_INIT = {"+": 0.0, "*": 1.0, "max": -np.inf, "min": np.inf}
_ACC_REDUCE = {"+": jnp.sum, "*": jnp.prod, "max": jnp.max, "min": jnp.min}


# ---------------------------------------------------------------------------
# Oracle: literal numpy interpreter
# ---------------------------------------------------------------------------
def execute_numpy(program: Program, inputs: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Interpret ``program`` literally in float64 numpy (the semantics oracle).

    Loops run point-by-point in authored order, so any transformed program
    whose outputs ``np.array_equal`` this one is bit-identical, not merely
    close.  Returns the full array environment (inputs copied, temps zeroed).
    """
    env = {
        a.name: (
            np.zeros(a.shape, dtype=np.float64)
            if a.name in program.temps
            else np.array(inputs[a.name], dtype=np.float64, copy=True)
        )
        for a in program.arrays
    }

    def eval_aff(a: Affine, it_env: dict[str, int]) -> int:
        """Evaluate an affine index expression under the iterator bindings."""
        return a.const + sum(c * it_env[k] for k, c in a.coeffs)

    def run(node: Node, it_env: dict[str, int]) -> None:
        """Execute one loop/computation node under the iterator bindings."""
        if isinstance(node, Computation):
            if any(eval_aff(g, it_env) < 0 for g in node.guards):
                return
            vals = []
            for r in node.reads:
                ix = tuple(eval_aff(e, it_env) for e in r.index)
                vals.append(env[r.array][ix] if ix else env[r.array][()])
            out = node.expr(*vals)
            wix = tuple(eval_aff(e, it_env) for e in node.write.index)
            tgt = env[node.write.array]
            if node.accumulate is None:
                tgt[wix] = out
            elif node.accumulate == "+":
                tgt[wix] += out
            elif node.accumulate == "*":
                tgt[wix] *= out
            elif node.accumulate == "max":
                tgt[wix] = max(tgt[wix], out)
            elif node.accumulate == "min":
                tgt[wix] = min(tgt[wix], out)
            else:
                raise ValueError(node.accumulate)
        else:
            for v in range(node.start, node.stop, node.step):
                it_env[node.iterator] = v
                for child in node.body:
                    run(child, it_env)
            it_env.pop(node.iterator, None)

    for n in program.body:
        run(n, {})
    return env


# ---------------------------------------------------------------------------
# JAX backend
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Schedule:
    """Scheduling decisions for ``compile_jax`` (one per top-level nest).

    The Pallas knobs select the grid-tiled lowering of canonical nests
    (``repro.core.tiling`` + ``repro.kernels.nest_kernel``): ``pallas_nest``
    routes fully-parallel nests (elementwise/stencil groups), ``pallas_reduce``
    routes associative reductions through a grid-accumulated scratch block.
    Nests outside the tiled class silently fall back to the generic
    vectorized path, so both flags are safe to set unconditionally.

    ``scan`` replaces the whole-array-carry ``lax.fori_loop`` lowering of
    carried (recurrence) loops with a ``lax.scan`` that slices leading-axis
    operands into per-step rows and stacks the written rows (canonical mode
    only; 'as_written' keeps the baseline-compiler fori behavior).

    ``shard_axis`` opts the nest into the mesh partitioner
    (``repro.core.partition``): when ``compile_sharded`` runs over a mesh
    axis of that name, the planner may shard the nest's outermost parallel
    iterator across it (None keeps the nest single-device/replicated).  The
    flag is inert under plain ``compile_jax``.
    """

    mode: str = "canonical"  # 'as_written' | 'canonical'
    use_idioms: bool = True  # BLAS-class dispatch (einsum / Pallas)
    vec_budget: int = 1 << 22  # max materialized elements per computation
    pallas_gemm: bool = False  # route GEMM idiom to the Pallas MXU kernel
    tile: tuple[int, int, int] | None = None  # Pallas GEMM block sizes
    interpret: bool | None = None  # Pallas interpret mode; None: off on a TPU only
    pallas_nest: bool = False  # grid-tiled Pallas for parallel nests
    pallas_reduce: bool = False  # grid-tiled Pallas for reduction nests
    nest_tile: tuple[int, ...] | None = None  # trailing-axis tiles (+red last)
    unroll: int = 1  # in-kernel reduction unroll factor
    scan: bool = True  # lax.scan recurrences (canonical mode)
    vmem_budget: int = 1 << 23  # tiling planner working-set budget (bytes)
    shard_axis: str | None = None  # mesh axis for the partition planner

    @property
    def interpret_kernels(self) -> bool:
        """Whether Pallas kernels run in the interpreter: ``interpret`` when
        set, else everywhere but on a TPU."""
        if self.interpret is not None:
            return self.interpret
        return pallas_backend() != "pallas"


# Trace-time lowering counters (tests assert which path actually fired);
# ``guard_disjoint`` counts loop iterators only the guard test vectorized.
LOWERING_STATS = {"scan": 0, "fori": 0, "guard_disjoint": 0}


@dataclass
class _VecAxis:
    iterator: str
    start: int
    stop: int
    step: int

    @property
    def trip(self) -> int:
        return max(0, (self.stop - self.start + self.step - 1) // self.step)


class Unsupported(Exception):
    """A nest shape the structured JAX lowering cannot express (caller falls
    back to the scan-based general path)."""


def _written_arrays(node: Node) -> list[str]:
    if isinstance(node, Computation):
        return [node.write.array]
    out: list[str] = []
    for _, c in walk(node):
        if c.write.array not in out:
            out.append(c.write.array)
    return out


def _is_multiplicative(expr: Callable, n_reads: int) -> float | None:
    """Probe: does ``expr(*xs) == c * prod(xs)``? Return c, else None.

    Memoized per ``expr`` object (weakly, so cached programs don't leak):
    the 4-numpy-probe answer is a pure function of the callable, and idiom
    detection re-asks it for every computation on every trace."""
    try:
        per_expr = _MULT_MEMO.setdefault(expr, {})
    except TypeError:  # not weakref-able (e.g. some builtins/partials)
        return _is_multiplicative_probe(expr, n_reads)
    if n_reads not in per_expr:
        per_expr[n_reads] = _is_multiplicative_probe(expr, n_reads)
    return per_expr[n_reads]


_MULT_MEMO: "weakref.WeakKeyDictionary[Callable, dict[int, float | None]]" = (
    weakref.WeakKeyDictionary()
)


def _is_multiplicative_probe(expr: Callable, n_reads: int) -> float | None:
    if n_reads == 0:
        return None
    rng = np.random.default_rng(0)
    try:
        c = float(expr(*([np.float64(1.0)] * n_reads)))
    except Exception:
        return None
    if not np.isfinite(c) or c == 0.0:
        return None
    for _ in range(3):
        xs = rng.uniform(0.5, 2.0, size=n_reads)
        try:
            got = float(expr(*[np.float64(x) for x in xs]))
        except Exception:
            return None
        want = c * float(np.prod(xs))
        if not np.isclose(got, want, rtol=1e-10, atol=1e-12):
            return None
    return c


def _single_iter_dims(a: Access) -> list[str] | None:
    """If every dim of ``a`` is exactly one iterator (coeff 1, const 0), return
    the iterator per dim; else None."""
    out = []
    for ix in a.index:
        if ix.const != 0 or len(ix.coeffs) != 1 or ix.coeffs[0][1] != 1:
            return None
        out.append(ix.coeffs[0][0])
    return out


def _offset_iter_dims(a: Access) -> list[tuple[str, int]] | None:
    """Like ``_single_iter_dims`` but tolerating constant offsets: per dim,
    ``(iterator, const)`` when the subscript is ``iterator + const`` (coeff 1);
    None when any dim is not of that shape."""
    out = []
    for ix in a.index:
        if len(ix.coeffs) != 1 or ix.coeffs[0][1] != 1:
            return None
        out.append((ix.coeffs[0][0], ix.const))
    return out


class _NestEmitter:
    """Emits one top-level nest into JAX, structure-driven."""

    def __init__(self, program: Program, schedule: Schedule):
        self.p = program
        self.s = schedule
        self.lowerings: list[str] = []  # outermost lowerings emitted, in order
        self.guard_disjoint = 0  # iterators the guard test made vectorizable
        self._depth = 0

    @contextmanager
    def _lowering(self, kind: str):
        """Emit the enclosed ops under ``jax.named_scope(kind)``; an outermost
        lowering that completes is added to ``self.lowerings``."""
        outer = self._depth == 0
        self._depth += 1
        try:
            with jax.named_scope(kind):
                yield
        finally:
            self._depth -= 1
        if outer and kind not in self.lowerings:
            self.lowerings.append(kind)

    # -- planning -----------------------------------------------------------
    def plan(self, nest: Node) -> dict[str, bool]:
        """iterator -> vectorizable? (plus budget-driven demotion).

        Legality is *per loop over its own subtree*: a loop may be
        materialized as an array axis iff no dependence among the
        computations it encloses is carried by its iterator.  Dependences
        between sibling nests are enforced by their sequential emission
        order and do not constrain vectorization.

        Guards enter legality in canonical mode only: a pair of accesses that
        the loop bounds and guards prove disjoint (``guarded_disjoint``)
        carries no dependence; ``self.guard_disjoint`` counts the iterators
        vectorized thanks to that test alone.
        """
        self.guard_disjoint = 0
        if isinstance(nest, Computation):
            return {}
        iterators = list(loop_iterators(nest))
        legal: dict[str, bool] = {}
        by_guards: set[str] = set()

        def carried(vecs) -> bool:
            return any(v.directions[0] != EQ for v in vecs)

        def visit(n: Node, outer: tuple[Loop, ...]) -> None:
            if isinstance(n, Computation):
                return
            placed = list(walk(n, outer))
            comps = [c for _, c in placed]
            trip = {n.iterator: n.trip_count}
            legal[n.iterator] = not carried(
                nest_direction_vectors([n.iterator], trip, comps))
            if not legal[n.iterator] and self.s.mode == "canonical":
                legal[n.iterator] = not carried(nest_direction_vectors(
                    [n.iterator], trip, comps, loops=[l for l, _ in placed]))
                if legal[n.iterator]:
                    by_guards.add(n.iterator)
            for b in n.body:
                visit(b, outer + (n,))

        visit(nest, ())
        if self.s.mode == "as_written":
            # only each computation's innermost enclosing loop is vectorized
            inner: set[str] = set()
            for loops, _ in walk(nest):
                if loops:
                    inner.add(loops[-1].iterator)
            return {it: (legal[it] and it in inner) for it in iterators}
        # canonical: vectorize all legal iterators within the budget,
        # demoting from the *outermost* side (keeps inner/fast axes wide).
        vec = {it: legal[it] for it in iterators}
        for loops, comp in walk(nest):
            used = [l for l in loops if vec.get(l.iterator)]
            prod = math.prod(max(1, l.trip_count) for l in used)
            for l in used:  # outermost first
                if prod <= self.s.vec_budget:
                    break
                vec[l.iterator] = False
                prod //= max(1, l.trip_count)
        self.guard_disjoint = sum(vec[it] for it in by_guards)
        return vec

    def _trips(self, nest: Node) -> dict[str, int]:
        out: dict[str, int] = {}

        def rec(n: Node) -> None:
            if isinstance(n, Loop):
                out[n.iterator] = n.trip_count
                for b in n.body:
                    rec(b)

        rec(nest)
        return out

    # -- emission -----------------------------------------------------------
    def emit(self, nest: Node, env: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        if self.s.pallas_nest or self.s.pallas_reduce:
            try:
                from ..kernels.nest_kernel import emit_nest

                return emit_nest(self.p, nest, env, self.s, lowering=self._lowering)
            except Unsupported:
                pass  # outside the tiled class: generic lowering below
        self.vec_plan = self.plan(nest)
        LOWERING_STATS["guard_disjoint"] += self.guard_disjoint
        return self._emit(nest, env, {}, [])

    def _emit(
        self,
        node: Node,
        env: dict[str, jnp.ndarray],
        seq_env: dict[str, Any],
        vec_axes: list[_VecAxis],
    ) -> dict[str, jnp.ndarray]:
        if isinstance(node, Computation):
            return self._emit_comp(node, env, seq_env, vec_axes)
        if self.vec_plan.get(node.iterator, False):
            vec2 = vec_axes + [_VecAxis(node.iterator, node.start, node.stop, node.step)]
            for child in node.body:
                env = self._emit(child, env, seq_env, vec2)
            return env
        if node.trip_count <= 0:
            return env
        # sequential loop: prefer the lax.scan lowering (leading-axis operands
        # become per-step slices; written rows are stacked instead of
        # scattered into a whole-array carry each iteration)
        if self.s.mode == "canonical" and self.s.scan:
            out = self._try_scan_loop(node, env, seq_env, vec_axes)
            if out is not None:
                LOWERING_STATS["scan"] += 1
                return out
        # fallback: lax.fori_loop carrying the written arrays whole
        carried = _written_arrays(node)
        LOWERING_STATS["fori"] += 1

        def body(k, carry):
            e = dict(env)
            e.update(dict(zip(carried, carry)))
            s2 = dict(seq_env)
            s2[node.iterator] = node.start + k * node.step
            for child in node.body:
                e = self._emit(child, e, s2, vec_axes)
            return tuple(e[a] for a in carried)

        with self._lowering("fori"):
            out = lax.fori_loop(0, node.trip_count, body, tuple(env[a] for a in carried))
        env = dict(env)
        env.update(dict(zip(carried, out)))
        return env

    # -- scan lowering of carried loops --------------------------------------
    def _scan_sliceable(self, node: Loop) -> tuple[dict[str, int], set[str]] | None:
        """Classify the arrays of a sequential loop's subtree.

        Returns ``(written_lookback, readonly)`` where ``written_lookback``
        maps each *written* array whose every access subscripts the leading
        axis with exactly ``t + const`` (write const 0, read consts <= 0) to
        its maximum lookback depth, and ``readonly`` holds read-only arrays
        accessed only at ``t`` itself.  None when no written array qualifies
        (scanning would buy nothing over fori)."""
        t = node.iterator
        status: dict[str, dict] = {}
        for _, c in walk(node):
            for a, is_w in [(c.write, True)] + [(r, False) for r in c.reads]:
                rec = status.setdefault(a.array, {"w": [], "r": [], "bad": False})
                ix0 = a.index[0] if a.index else None
                uses_t = any(ix.coeff(t) != 0 for ix in a.index)
                if ix0 is not None and ix0.coeffs == ((t, 1),) and not any(
                    ix.coeff(t) != 0 for ix in a.index[1:]
                ):
                    (rec["w"] if is_w else rec["r"]).append(ix0.const)
                elif uses_t:
                    rec["bad"] = True
                else:
                    rec.setdefault("plain", True)  # t-independent access
        written_lb: dict[str, int] = {}
        readonly: set[str] = set()
        for name, rec in status.items():
            if rec["bad"] or rec.get("plain"):
                continue
            if rec["w"]:
                if all(c == 0 for c in rec["w"]) and all(c <= 0 for c in rec["r"]):
                    written_lb[name] = max([0] + [-c for c in rec["r"]])
            elif rec["r"] and all(c == 0 for c in rec["r"]):
                readonly.add(name)
        if not written_lb:
            return None
        return written_lb, readonly

    def _try_scan_loop(self, node: Loop, env, seq_env, vec_axes):
        if node.step != 1:
            return None
        cls = self._scan_sliceable(node)
        if cls is None:
            return None
        written_lb, readonly = cls
        for name in list(written_lb) + sorted(readonly):
            arr = env[name]
            if arr.ndim == 0 or node.start + node.trip_count > arr.shape[0]:
                return None  # leading axis does not cover the loop range
        with self._lowering("scan"):
            return self._scan_loop(node, env, seq_env, vec_axes, written_lb, readonly)

    def _scan_loop(self, node: Loop, env, seq_env, vec_axes, written_lb, readonly):
        t, start, n = node.iterator, node.start, node.trip_count
        sliceable = set(written_lb) | readonly

        def lag_name(a: str, d: int) -> str:
            return f"{a}@lag{d}"

        def rw_access(a: Access) -> Access:
            if a.array not in sliceable:
                return a
            c = a.index[0].const
            nm = a.array if c == 0 else lag_name(a.array, -c)
            return Access(nm, a.index[1:])

        def rw(nd: Node) -> Node:
            if isinstance(nd, Computation):
                return dc_replace(
                    nd,
                    write=rw_access(nd.write),
                    reads=tuple(rw_access(r) for r in nd.reads),
                )
            return dc_replace(nd, body=tuple(rw(b) for b in nd.body))

        children = tuple(rw(ch) for ch in node.body)
        whole_written = [a for a in _written_arrays(node) if a not in written_lb]

        xs = {}
        for a in sliceable:
            arr = env[a]
            xs[a] = arr if (start == 0 and n == arr.shape[0]) else lax.slice(
                arr, [start] + [0] * (arr.ndim - 1),
                [start + n] + list(arr.shape[1:]))
        vks = start + jnp.arange(n, dtype=jnp.int32)
        lags0 = {
            lag_name(a, d): env[a][(start - d) % env[a].shape[0]]
            for a, lb in written_lb.items() for d in range(1, lb + 1)
        }
        whole0 = {a: env[a] for a in whole_written}

        def body(carry, x):
            lags, whole = carry
            vk, slabs = x
            e = dict(env)
            e.update(whole)
            e.update(slabs)
            e.update(lags)
            s2 = dict(seq_env)
            s2[t] = vk
            for ch in children:
                e = self._emit(ch, e, s2, vec_axes)
            new_lags = {}
            for a, lb in written_lb.items():
                if lb >= 1:
                    new_lags[lag_name(a, 1)] = e[a]
                for d in range(2, lb + 1):
                    new_lags[lag_name(a, d)] = lags[lag_name(a, d - 1)]
            return (new_lags, {a: e[a] for a in whole}), {
                a: e[a] for a in written_lb}

        (_, whole_f), ys = lax.scan(body, (lags0, whole0), (vks, xs))
        env = dict(env)
        for a in written_lb:
            arr = env[a]
            rows = ys[a].astype(arr.dtype)
            env[a] = rows if (start == 0 and n == arr.shape[0]) else (
                lax.dynamic_update_slice(
                    arr, rows, [start] + [0] * (arr.ndim - 1)))
        env.update(whole_f)
        return env

    # -- computation emission -----------------------------------------------
    def _axes_for(self, comp: Computation, vec_axes: list[_VecAxis]) -> list[_VecAxis]:
        used = set(comp.iterators())
        return [a for a in vec_axes if a.iterator in used]

    def _iter_value(self, it: str, axes: list[_VecAxis], seq_env: dict[str, Any]):
        for pos, a in enumerate(axes):
            if a.iterator == it:
                r = a.start + a.step * jnp.arange(a.trip, dtype=jnp.int32)
                shape = [1] * len(axes)
                shape[pos] = a.trip
                return r.reshape(shape)
        if it in seq_env:
            return seq_env[it]
        raise Unsupported(f"iterator {it} not bound")

    def _eval_affine(self, e: Affine, axes: list[_VecAxis], seq_env: dict[str, Any]):
        val = e.const
        for it, c in e.coeffs:
            val = val + c * self._iter_value(it, axes, seq_env)
        return val

    def _fast_read(self, a: Access, arr, axes: list[_VecAxis]):
        """Direct (possibly sliced/transposed) array view when every dim of
        ``a`` is a distinct vectorized axis up to a constant offset — avoids
        materializing iota index grids and a gather per access, which XLA
        fuses far worse than the plain slice+transpose+reshape this emits
        (dominant for re-fused elementwise chains and constant-offset
        stencil reads like ``A[i-1, j]``)."""
        its_c = _offset_iter_dims(a)
        if its_c is None or len(its_c) != arr.ndim:
            return None
        its = [it for it, _ in its_c]
        if len(set(its)) != len(its):
            return None
        axis_of = {ax.iterator: k for k, ax in enumerate(axes)}
        if not all(it in axis_of for it in its):
            return None
        lo = []
        for d, (it, c) in enumerate(its_c):
            ax = axes[axis_of[it]]
            start = ax.start + c
            if ax.step != 1 or start < 0 or start + ax.trip > arr.shape[d]:
                return None
            lo.append(start)
        if any(lo) or any(axes[axis_of[it]].trip != arr.shape[d]
                          for d, it in enumerate(its)):
            arr = lax.slice(
                arr, lo, [s + axes[axis_of[it]].trip
                          for s, it in zip(lo, its)])
        order = sorted(range(arr.ndim), key=lambda d: axis_of[its[d]])
        out = jnp.transpose(arr, order) if order != list(range(arr.ndim)) else arr
        shape = [1] * len(axes)
        for d, it in enumerate(its):
            shape[axis_of[it]] = arr.shape[d]
        return out.reshape(shape)

    def _gather(self, a: Access, env, axes, seq_env):
        arr = env[a.array]
        if not a.index:
            return arr
        fast = self._fast_read(a, arr, axes)
        if fast is not None:
            return fast
        idx = tuple(self._eval_affine(ix, axes, seq_env) for ix in a.index)
        if all(np.isscalar(i) or (hasattr(i, "ndim") and i.ndim == 0) for i in idx):
            return arr[idx]
        # broadcast scalar dims to arrays for advanced indexing
        shape = jnp.broadcast_shapes(*[jnp.shape(i) for i in idx if hasattr(i, "shape")] or [()])
        idx = tuple(jnp.broadcast_to(jnp.asarray(i, jnp.int32), shape) for i in idx)
        return arr[idx]

    def _emit_comp(self, comp, env, seq_env, vec_axes):
        axes = self._axes_for(comp, vec_axes)
        if self.s.use_idioms:
            out = self._try_einsum(comp, env, seq_env, axes)
            if out is not None:
                env = dict(env)
                env[comp.write.array] = out
                return env
        with self._lowering("vectorize"):
            return self._emit_vector(comp, env, seq_env, axes)

    def _emit_vector(self, comp, env, seq_env, axes):
        """The generic lowering of one computation over its vector axes:
        gather the reads, evaluate, mask, reduce and write back."""
        vals = comp.expr(*[self._gather(r, env, axes, seq_env) for r in comp.reads])
        full_shape = tuple(a.trip for a in axes)
        vals = jnp.broadcast_to(vals, jnp.broadcast_shapes(jnp.shape(vals), full_shape))

        mask = None
        for g in comp.guards:
            gv = self._eval_affine(g, axes, seq_env)
            m = jnp.broadcast_to(jnp.asarray(gv) >= 0, full_shape)
            mask = m if mask is None else (mask & m)

        # split axes into write (kept) vs reduction (folded)
        w_its = set(it for ix in comp.write.index for it in ix.iterators())
        keep = [k for k, a in enumerate(axes) if a.iterator in w_its]
        red = [k for k, a in enumerate(axes) if a.iterator not in w_its]
        acc = comp.accumulate
        if red and acc is None:
            raise Unsupported(f"{comp.name}: assignment under reduction axes")
        if mask is not None and acc is not None:
            fill = _ACC_INIT[acc]
            vals = jnp.where(mask, vals, fill)
        if red:
            vals = _ACC_REDUCE[acc](vals, axis=tuple(red))
        kept_axes = [axes[k] for k in keep]

        arr = env[comp.write.array]
        env = dict(env)
        if not comp.write.index:  # scalar (0-d) target
            if acc is None:
                new = jnp.where(mask, vals, arr) if mask is not None else vals
            else:
                new = _combine(acc, arr, vals)
            env[comp.write.array] = new.astype(arr.dtype)
            return env

        # fast path: write map is a permutation of kept axes addressing a
        # contiguous region of the array (identity scatter / interior slice)
        # (for accumulates, any mask was already folded into neutral fills)
        fast = self._fast_write(comp, kept_axes, arr)
        if fast is not None:
            perm, los, full = fast
            vt = jnp.transpose(vals, perm) if perm != tuple(range(vals.ndim)) else vals
            old = arr if full else lax.slice(
                arr, los, [lo + kept_axes[p].trip for lo, p in zip(los, perm)])
            if acc is None:
                if mask is not None:
                    mt = jnp.transpose(mask, perm) if perm != tuple(range(mask.ndim)) else mask
                    # mask covers only kept axes here (no reduction with set)
                    vt = jnp.where(mt, vt, old)
                new = vt.astype(arr.dtype)
            else:
                new = _combine(acc, old, vt).astype(arr.dtype)
            env[comp.write.array] = (
                new if full else lax.dynamic_update_slice(arr, new, los))
            return env

        widx = tuple(
            jnp.broadcast_to(
                jnp.asarray(self._eval_affine(ix, kept_axes, seq_env), jnp.int32),
                tuple(a.trip for a in kept_axes),
            )
            for ix in comp.write.index
        )
        if acc is None:
            if mask is not None:
                # set-writes have no reduction axes, so mask is over kept axes
                cur = arr[widx]
                vals = jnp.where(mask, vals, cur)
            env[comp.write.array] = arr.at[widx].set(vals.astype(arr.dtype))
        else:
            upd = getattr(arr.at[widx], {"+": "add", "*": "multiply", "max": "max", "min": "min"}[acc])
            env[comp.write.array] = upd(vals.astype(arr.dtype))
        return env

    def _fast_write(self, comp, kept_axes, arr):
        """Return ``(perm, origins, full_cover)`` when the write map is a
        permutation of the kept vectorized axes addressing a contiguous
        in-bounds region (constant offsets and non-zero loop starts allowed:
        stencil interiors update via slice instead of an index-grid scatter).
        """
        its_c = _offset_iter_dims(comp.write)
        if its_c is None or len(its_c) != arr.ndim:
            return None
        its = [it for it, _ in its_c]
        axis_of = {a.iterator: k for k, a in enumerate(kept_axes)}
        if set(its) != set(axis_of) or len(set(its)) != len(its):
            return None
        los, full = [], True
        for d, (it, c) in enumerate(its_c):
            a = kept_axes[axis_of[it]]
            lo = a.start + c
            if a.step != 1 or lo < 0 or lo + a.trip > arr.shape[d]:
                return None
            los.append(lo)
            full = full and lo == 0 and a.trip == arr.shape[d]
        return tuple(axis_of[it] for it in its), tuple(los), full

    # -- BLAS idiom: einsum / Pallas GEMM ------------------------------------
    def _try_einsum(self, comp, env, seq_env, axes):
        if comp.accumulate != "+" or comp.guards or len(comp.reads) < 1:
            return None
        c = _is_multiplicative(comp.expr, len(comp.reads))
        if c is None:
            return None
        ax_of = {a.iterator: a for a in axes}
        # every iterator of the computation must be a vectorized full-range axis
        for it in comp.iterators():
            a = ax_of.get(it)
            if a is None or a.start != 0 or a.step != 1:
                return None
        # accesses: dims are single iterators (full range) or seq-env scalars
        def classify(a: Access):
            letters, slicers = [], []
            arr = env[a.array]
            for d, ix in enumerate(a.index):
                its = ix.iterators()
                if len(its) == 1 and ix.const == 0 and ix.coeff(its[0]) == 1 and its[0] in ax_of:
                    if ax_of[its[0]].trip != arr.shape[d]:
                        return None
                    letters.append(its[0])
                    slicers.append(None)
                elif not its or all(it in seq_env for it in its):
                    slicers.append(self._eval_affine(ix, [], seq_env))
                    letters.append(None)
                else:
                    return None
            return letters, slicers

        w = classify(comp.write)
        if w is None or any(l is None for l in w[0]):
            return None
        rs = [classify(r) for r in comp.reads]
        if any(r is None for r in rs):
            return None

        sym: dict[str, str] = {}

        def letter(it: str) -> str:
            if it not in sym:
                sym[it] = "abcdefghijklmnopqrstuvwxyz"[len(sym)]
            return sym[it]

        operands, subs = [], []
        for (letters, slicers), acc_r in zip(rs, comp.reads):
            arr = env[acc_r.array]
            sub = ""
            for d in range(len(letters) - 1, -1, -1):
                if letters[d] is None:
                    arr = jnp.take(arr, jnp.asarray(slicers[d], jnp.int32), axis=d)
            for d, l in enumerate(letters):
                if l is not None:
                    sub += letter(l)
            operands.append(arr)
            subs.append(sub)
        out_sub = "".join(letter(l) for l in w[0])
        for l in out_sub:
            if not any(l in s for s in subs):
                return None  # output iterator never read: einsum can't broadcast it
        arr = env[comp.write.array]
        if tuple(ax_of[l].trip for l in w[0]) != arr.shape:
            return None  # partial-cover writes take the generic path
        if self.s.pallas_gemm and len(operands) == 2:
            # canonical 2-operand contraction -> Pallas MXU kernel; a
            # contraction the GEMM cannot express takes jnp.einsum
            from ..kernels import ops as kops

            try:
                with self._lowering("pallas_gemm"):
                    return _scaled_add(arr, c, kops.einsum2(
                        subs[0], subs[1], out_sub, operands[0], operands[1],
                        tile=self.s.tile, interpret=self.s.interpret_kernels,
                    ))
            except kops.NotAContraction:
                pass
        with self._lowering("einsum"):
            return _scaled_add(arr, c, jnp.einsum(",".join(subs) + "->" + out_sub, *operands))


def _scaled_add(arr, c: float, contrib):
    if c != 1.0:
        contrib = contrib * c
    return arr + contrib.astype(arr.dtype)


def _combine(acc: str, a, b):
    return {"+": lambda: a + b, "*": lambda: a * b,
            "max": lambda: jnp.maximum(a, b), "min": lambda: jnp.minimum(a, b)}[acc]()


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def compile_jax(
    program: Program,
    per_nest: Schedule | Sequence[Schedule] = Schedule(),
) -> Callable[[Mapping[str, Any]], dict[str, Any]]:
    """Build a jit-able fn: {array: value} -> {array: value} (updated).

    ``per_nest`` is one ``Schedule`` per top-level nest (the daisy scheduler
    resolves one recipe per canonical nest); a single ``Schedule`` is
    broadcast to every nest.
    """
    if isinstance(per_nest, Schedule):
        schedules: Sequence[Schedule] = (per_nest,) * len(program.body)
    else:
        schedules = tuple(per_nest)
        if len(schedules) != len(program.body):
            raise ValueError(
                f"{program.name}: got {len(schedules)} schedules for "
                f"{len(program.body)} top-level nests"
            )

    def fn(inputs: Mapping[str, Any]) -> dict[str, Any]:
        """Run every nest under its schedule; returns the array environment."""
        with span("codegen.emit", program=program.name):
            env = {
                a.name: (
                    jnp.zeros(a.shape, dtype=jnp.float32)
                    if a.name in program.temps
                    else jnp.asarray(inputs[a.name])
                )
                for a in program.arrays
            }
            for i, (nest, sched) in enumerate(zip(program.body, schedules)):
                env = _emit_top_nest(program, i, nest, sched, env)
        return env

    fn.__name__ = fn.__qualname__ = module_name(program.name)
    return fn


def _emit_top_nest(program: Program, index: int, nest: Node, schedule: Schedule,
                   env: dict[str, Any]) -> dict[str, Any]:
    """Emit top-level nest ``index`` under ``jax.named_scope(f"nest{index}")``,
    recorded as a ``codegen.nest`` span with the lowering(s) it took and the
    number of iterators the guard test vectorized (``guard_disjoint``)."""
    with span("codegen.nest", index=index) as s, jax.named_scope(f"nest{index}"):
        em = _NestEmitter(program, schedule)
        env = em.emit(nest, env)
        s.attrs["lowering"] = "+".join(em.lowerings)
        s.attrs["guard_disjoint"] = em.guard_disjoint
    return env


def run_jax(
    program: Program,
    inputs: Mapping[str, Any],
    per_nest: Schedule | Sequence[Schedule] | None = None,
):
    """Compile ``program`` with ``compile_jax``, jit it, and run it once."""
    sched = per_nest if per_nest is not None else Schedule()
    return jax.jit(compile_jax(program, sched))(dict(inputs))
