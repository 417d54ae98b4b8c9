"""Sharded execution of canonical programs (data-parallel mesh partitioning).

The paper's flagship application is embarrassingly parallel over horizontal
grid columns (CLOUDSC's NPROMA blocking, §5.2); after a priori normalization
the minimal-stride permutation has already surfaced that parallel iterator in
every canonical nest.  This module picks it up and maps it onto a mesh axis:

* ``plan_program_partition`` — the planner.  Per canonical nest it walks the
  iterators outermost-first and selects the first *parallel* iterator (no
  dependence carried by it, per the same direction-vector oracle the
  normalizer uses) whose accesses are **shard-aligned**: the iterator appears
  in exactly one dimension of every access that uses it, with coefficient 1
  and offset 0, covering the full array extent.  Everything else vetoes:

    - carried / scan iterators (recurrences)        -> try the next iterator
    - constant-offset or strided use (``A[p-1]``)   -> cross-shard flow, veto
    - guards referencing the iterator               -> shard-position
      dependent control flow, veto
    - accumulations over the sharded iterator whose extent does not divide
      the mesh (padding would feed garbage into the all-reduce), veto

  A nest with no shardable iterator falls back to replication, and every
  array it touches is pinned replicated program-wide (the plan restarts until
  the array assignment is globally consistent — one ``PartitionSpec`` per
  array for the whole program).

* ``compile_sharded`` — the executor.  Builds the shard-local program (loop
  extents and array dims divided by the mesh axis, padded up when the extent
  does not divide), emits each nest through the existing per-nest lowering
  (``_NestEmitter`` under its ``nest<i>`` scope: einsum idioms, Pallas
  kernels, scan recurrences — all unchanged inside the shard), inserts the
  all-reduce (``psum``/``pmax``/``pmin``) after nests that accumulate over
  their sharded iterator, and wraps the whole body in ``shard_map`` with one
  ``PartitionSpec`` per array.  The function is named after the program, as
  ``compile_jax``'s is.
  When nothing shards (or the mesh axis is 1) it returns the plain
  single-device lowering — sharding is always a sound no-op to request.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from .codegen import Schedule, _emit_top_nest, compile_jax
from .dependence import EQ, nest_direction_vectors
from .ir import (
    Array,
    Computation,
    Loop,
    Node,
    Program,
    loop_iterators,
    nest_computations,
    walk,
)
from .spans import module_name, span

# accumulate ops with a mesh collective (no pprod exists; '*' stays vetoed)
_SHARD_REDUCE = {"+", "max", "min"}


@dataclass(frozen=True)
class NestPartition:
    """Sharding decision for one top-level nest."""

    iterator: str | None                       # None -> replicated fallback
    reduces: tuple[tuple[str, str], ...] = ()  # (array, op) all-reduced after
    reason: str = "sharded"                    # veto reason when iterator=None


@dataclass
class ProgramPartition:
    """Whole-program sharding plan: one spec per array, one choice per nest."""

    axis: str
    n_shards: int
    array_dims: dict[str, int | None]  # array -> sharded dim (None: replicated)
    nests: list[NestPartition] = field(default_factory=list)

    @property
    def sharded(self) -> bool:
        """True when at least one nest actually shards an iterator."""
        return any(n.iterator is not None for n in self.nests)

    def padded_extent(self, extent: int) -> int:
        """``extent`` rounded up to a multiple of the shard count."""
        return -(-extent // self.n_shards) * self.n_shards

    def spec(self, shape: tuple[int, ...], name: str) -> PartitionSpec:
        """The ``PartitionSpec`` for array ``name`` under this plan."""
        d = self.array_dims.get(name)
        return PartitionSpec(*[self.axis if i == d else None
                               for i in range(len(shape))])

    def describe(self) -> str:
        """Human-readable rendering of the per-nest/per-array decisions."""
        lines = [f"partition over axis '{self.axis}' x{self.n_shards}:"]
        for k, np_ in enumerate(self.nests):
            if np_.iterator is None:
                lines.append(f"  nest {k}: replicated ({np_.reason})")
            else:
                red = "".join(f" all-reduce({a},{op})" for a, op in np_.reduces)
                lines.append(f"  nest {k}: shard {np_.iterator}{red}")
        reps = sorted(a for a, d in self.array_dims.items() if d is None)
        shs = {a: d for a, d in self.array_dims.items() if d is not None}
        lines.append("  arrays: " + ", ".join(
            [f"{a}@dim{d}" for a, d in sorted(shs.items())] + reps))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# per-nest candidate analysis
# ---------------------------------------------------------------------------
def _loops_of(nest: Node) -> dict[str, Loop]:
    out: dict[str, Loop] = {}

    def rec(n: Node) -> None:
        if isinstance(n, Loop):
            out[n.iterator] = n
            for b in n.body:
                rec(b)

    rec(nest)
    return out


def _nest_arrays(nest: Node) -> set[str]:
    return {a.array for c in nest_computations(nest) for a in c.accesses()}


def _candidate(
    program: Program, nest: Loop, p: str, n_shards: int
) -> tuple[dict[str, tuple], dict[str, str]] | str:
    """Try sharding ``nest`` over iterator ``p``.

    Returns ``(requirements, reduces)`` — ``requirements`` maps each touched
    array to ``('dim', d)`` (shard on dim d) or ``('rep',)`` (replicate),
    ``reduces`` maps accumulated arrays to their all-reduce op — or a veto
    reason string.
    """
    loop = _loops_of(nest)[p]
    if loop.start != 0 or loop.step != 1:
        return f"{p}: non-canonical bounds [{loop.start}::{loop.step}]"
    if loop.trip_count < n_shards:
        return f"{p}: extent {loop.trip_count} < {n_shards} shards"

    # parallel? no dependence among the loop's own computations carried by p
    comps_p = nest_computations(loop)
    vecs = nest_direction_vectors([p], {p: loop.trip_count}, comps_p)
    if not all(v.directions[0] == EQ for v in vecs):
        return f"{p}: carried dependence (recurrence stays per-shard-serial)"

    req: dict[str, tuple] = {}
    reduces: dict[str, str] = {}

    def merge(arr: str, want: tuple) -> str | None:
        have = req.get(arr)
        if have is None or have == want:
            req[arr] = want
            return None
        return f"{arr}: conflicting shard requirements {have} vs {want}"

    for _, comp in walk(nest):
        uses_p = p in comp.iterators()
        if any(g.coeff(p) != 0 for g in comp.guards):
            return f"{p}: guard of '{comp.name}' references the shard iterator"
        for a, is_write in [(comp.write, True)] + [(r, False) for r in comp.reads]:
            dims_p = [d for d, ix in enumerate(a.index) if ix.coeff(p) != 0]
            if not dims_p:
                if is_write and uses_p:
                    # value varies with p, write target does not: a reduction
                    # over the sharded iterator -> all-reduce after the nest
                    if comp.accumulate not in _SHARD_REDUCE:
                        return (f"{p}: '{comp.name}' writes {a.array} without "
                                f"an all-reducible accumulate")
                    if loop.trip_count % n_shards != 0:
                        return (f"{p}: reduction over a padded extent "
                                f"({loop.trip_count} % {n_shards} != 0)")
                    prev = reduces.setdefault(a.array, comp.accumulate)
                    if prev != comp.accumulate:
                        return f"{a.array}: mixed reduce ops {prev}/{comp.accumulate}"
                    err = merge(a.array, ("rep",))
                else:
                    # access never sees p -> this nest needs the array whole
                    err = merge(a.array, ("rep",))
                if err:
                    return err
                continue
            if len(dims_p) != 1:
                return f"{p}: {a.array} uses the shard iterator in two dims"
            d = dims_p[0]
            ix = a.index[d]
            if ix.coeffs != ((p, 1),) or ix.const != 0:
                return (f"{p}: {a.array}[..{ix!r}..] is offset/strided — "
                        "cross-shard flow")
            arr = program.array(a.array)
            if loop.stop != arr.shape[d]:
                return (f"{p}: loop [0:{loop.stop}] covers {a.array} dim {d} "
                        f"({arr.shape[d]}) partially")
            err = merge(a.array, ("dim", d))
            if err:
                return err
    # the all-reduce runs only after the whole nest: any read of a reduce
    # target inside the nest (e.g. a sibling computation outside the
    # candidate loop, or an explicit self-read) would observe per-shard
    # partial sums -> veto
    for arr in reduces:
        for c in nest_computations(nest):
            if any(r.array == arr for r in c.reads):
                return (f"{arr}: reduce target read inside the nest "
                        "(partial sums would be visible)")
    return req, reduces


# ---------------------------------------------------------------------------
# program-level planning
# ---------------------------------------------------------------------------
def plan_program_partition(
    program: Program,
    n_shards: int,
    axis: str = "data",
    enabled: Sequence[bool] | None = None,
) -> ProgramPartition:
    """One consistent sharding plan for the whole (normalized) program.

    Greedy over nests in program order, outermost iterator first; arrays get
    exactly one spec program-wide.  When a replicated nest touches an array
    an earlier nest sharded, that array is pinned replicated and planning
    restarts (bounded by the array count), so the result is always globally
    consistent — nests that cannot agree simply stay replicated.
    """
    if enabled is None:
        enabled = [True] * len(program.body)
    forced_rep: set[str] = set()
    for _ in range(len(program.arrays) + 1):
        assigned: dict[str, int | None] = {}
        nests: list[NestPartition] = []
        restart = False
        for nest, en in zip(program.body, enabled):
            chosen: NestPartition | None = None
            chosen_req: dict[str, tuple] = {}
            reason = "sharding disabled for this nest"
            # arrays whose *replication* would admit this nest's best
            # candidate (it needs them whole — e.g. as all-reduce targets —
            # while an earlier nest sharded them).  Replicating an array is
            # always sound, so prefer unlocking this nest over keeping a
            # possibly-trivial earlier sharding.
            unlockable: set[str] | None = None
            if en and isinstance(nest, Loop):
                for p in loop_iterators(nest):
                    cand = _candidate(program, nest, p, n_shards)
                    if isinstance(cand, str):
                        if reason == "sharding disabled for this nest":
                            reason = cand  # outermost veto, for diagnostics
                        continue
                    req, reduces = cand
                    clashes: set[str] = set()
                    fixable = True
                    for arr, want in req.items():
                        d = want[1] if want[0] == "dim" else None
                        if (d is not None and arr in forced_rep) or (
                            arr in assigned and assigned[arr] != d
                        ):
                            clashes.add(arr)
                            # only a want-replicated / have-sharded clash is
                            # curable by forcing replication
                            if d is not None:
                                fixable = False
                    if not clashes:
                        chosen = NestPartition(p, tuple(sorted(reduces.items())))
                        chosen_req = req
                        break
                    if reason == "sharding disabled for this nest":
                        reason = (f"{p}: array spec conflict on "
                                  f"{'/'.join(sorted(clashes))} (replicated "
                                  "for whole-program consistency)")
                    if unlockable is None and fixable:
                        unlockable = clashes
            if chosen is None:
                if unlockable:
                    forced_rep |= unlockable
                    restart = True
                    break
                touched = _nest_arrays(nest)
                conflict = {a for a in touched if assigned.get(a) is not None}
                if conflict:
                    forced_rep |= conflict
                    restart = True
                    break
                for a in touched:
                    assigned.setdefault(a, None)
                nests.append(NestPartition(None, reason=reason))
            else:
                for arr, want in chosen_req.items():
                    assigned[arr] = want[1] if want[0] == "dim" else None
                nests.append(chosen)
        if not restart:
            for a in program.arrays:  # untouched arrays stay replicated
                assigned.setdefault(a.name, None)
            return ProgramPartition(axis, n_shards, assigned, nests)
    raise AssertionError("partition planning failed to converge")  # pragma: no cover


# ---------------------------------------------------------------------------
# shard-local program + executor
# ---------------------------------------------------------------------------
def _rewrite_extent(node: Node, iterator: str, stop: int) -> Node:
    if isinstance(node, Computation):
        return node
    body = tuple(_rewrite_extent(b, iterator, stop) for b in node.body)
    if node.iterator == iterator:
        return replace(node, stop=stop, body=body)
    return replace(node, body=body)


def local_program(program: Program, plan: ProgramPartition) -> Program:
    """The per-shard program: sharded dims and loop extents divided (padded
    up to the mesh first when the extent does not divide)."""
    n = plan.n_shards
    arrays = []
    for a in program.arrays:
        d = plan.array_dims.get(a.name)
        if d is None:
            arrays.append(a)
        else:
            shape = list(a.shape)
            shape[d] = plan.padded_extent(shape[d]) // n
            arrays.append(Array(a.name, tuple(shape), a.dtype))
    body = []
    for nest, np_ in zip(program.body, plan.nests):
        if np_.iterator is None:
            body.append(nest)
        else:
            ext = plan.padded_extent(_loops_of(nest)[np_.iterator].stop) // n
            body.append(_rewrite_extent(nest, np_.iterator, ext))
    return Program(program.name, tuple(arrays), tuple(body), program.temps)


def _all_reduce(op: str, old, new, axis: str):
    if op == "+":
        # accumulate folds into the (replicated) prior contents: sum only
        # the per-shard contributions, then add the base back once
        return old + lax.psum(new - old, axis)
    if op == "max":
        return lax.pmax(new, axis)
    return lax.pmin(new, axis)


def compile_sharded(
    program: Program,
    per_nest: Schedule | Sequence[Schedule] = Schedule(),
    mesh: Any = None,
    axis: str = "data",
) -> tuple[Callable[[Mapping[str, Any]], dict[str, Any]], ProgramPartition]:
    """Like ``compile_jax`` but executed across ``mesh``'s ``axis``.

    Nests whose ``Schedule.shard_axis`` names ``axis`` are considered for
    sharding (a broadcast single Schedule enables every nest); the planner
    still vetoes per nest.  Returns ``(fn, plan)`` — when nothing shards the
    fn IS the single-device lowering and the plan records every veto reason.
    """
    if isinstance(per_nest, Schedule):
        schedules: Sequence[Schedule] = (per_nest,) * len(program.body)
    else:
        schedules = tuple(per_nest)
        if len(schedules) != len(program.body):
            raise ValueError(
                f"{program.name}: got {len(schedules)} schedules for "
                f"{len(program.body)} top-level nests")
    n = int(mesh.shape[axis]) if mesh is not None else 1
    if n <= 1:  # degenerate mesh: report an honest all-replicated plan
        enabled: Sequence[bool] = [False] * len(program.body)
    else:
        enabled = [s.shard_axis == axis for s in schedules]
    plan = plan_program_partition(program, max(n, 1), axis, enabled)
    if mesh is None or n <= 1 or not plan.sharded:
        return compile_jax(program, schedules), plan

    local = local_program(program, plan)
    in_names = [a.name for a in program.input_arrays]
    all_names = [a.name for a in program.arrays]
    shapes = {a.name: a.shape for a in program.arrays}

    def local_fn(*vals):
        """Per-shard body: run every nest locally, all-reducing as planned."""
        with span("codegen.emit", program=program.name):
            env: dict[str, jnp.ndarray] = {}
            lvals = dict(zip(in_names, vals))
            for a in local.arrays:
                env[a.name] = (jnp.zeros(a.shape, jnp.float32)
                               if a.name in local.temps else lvals[a.name])
            for i, (nest, sched, np_) in enumerate(zip(local.body, schedules, plan.nests)):
                old = {arr: env[arr] for arr, _ in np_.reduces}
                env = _emit_top_nest(local, i, nest, sched, env)
                for arr, op in np_.reduces:
                    env[arr] = _all_reduce(op, old[arr], env[arr], axis)
        return tuple(env[k] for k in all_names)

    # replicated out-specs (redundantly computed arrays, all-reduced
    # accumulators) are not always provable by the static replication
    # checker; the planner's veto analysis is the soundness argument
    sm = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=tuple(plan.spec(shapes[k], k) for k in in_names),
        out_specs=tuple(plan.spec(shapes[k], k) for k in all_names),
        check_vma=False,
    )

    def fn(inputs: Mapping[str, Any]) -> dict[str, Any]:
        """Pad inputs to shard multiples, run the shard map, unpad outputs."""
        vals = []
        for k in in_names:
            v = jnp.asarray(inputs[k])
            d = plan.array_dims.get(k)
            if d is not None:
                pad = plan.padded_extent(v.shape[d]) - v.shape[d]
                if pad:
                    widths = [(0, pad if i == d else 0) for i in range(v.ndim)]
                    v = jnp.pad(v, widths)
            vals.append(v)
        outs = dict(zip(all_names, sm(*vals)))
        for k, v in outs.items():
            d = plan.array_dims.get(k)
            if d is not None and v.shape[d] != shapes[k][d]:
                outs[k] = lax.slice(
                    v, [0] * v.ndim,
                    [shapes[k][i] if i == d else s
                     for i, s in enumerate(v.shape)])
        return outs

    fn.__name__ = fn.__qualname__ = module_name(program.name)
    return fn, plan


def run_sharded(
    program: Program,
    inputs: Mapping[str, Any],
    mesh: Any,
    per_nest: Schedule | Sequence[Schedule] | None = None,
    axis: str = "data",
):
    """One-shot jitted sharded execution (mirrors ``run_jax``)."""
    sched = per_nest if per_nest is not None else Schedule(shard_axis=axis)
    fn, _ = compile_sharded(program, sched, mesh=mesh, axis=axis)
    return jax.jit(fn)(dict(inputs))
