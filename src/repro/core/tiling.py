"""Grid-tiling planner for canonical nests (the Pallas lowering's front half).

Normalization collapses loop-nest variants onto canonical forms; this module
decides how a canonical nest's iteration space maps onto a Pallas grid:

* **parallel iterators** (no carried dependence, appear in every write) become
  grid dimensions, each partitioned into VPU-aligned tiles ``(…, sublane=8k,
  lane=128k)``;
* for reductions, the **innermost reduction iterator** becomes one extra
  'arbitrary' grid dimension accumulated through a VMEM scratch block (the
  GEMM pattern generalized to any associative accumulate), while outer
  reduction iterators stay whole inside the tile;
* **constant-offset reads** (stencils) and non-zero loop starts are handled by
  halo padding: the planner computes, per array dimension, how far accesses
  reach outside ``[0, extent)`` so the emitter can pad-and-shift each operand
  into a view whose blocks are exactly tile-aligned (one BlockSpec per affine
  access map — overlapping halo reads become *distinct operands*, which is
  how Pallas expresses them without giving up blocked pipelining).

Tile sizes come from the recipe (``Recipe.tile`` / ``Schedule.nest_tile``,
assigned to the innermost axes) or default to whole extents shrunk until the
estimated VMEM working set — the sum of all operand blocks plus the
accumulator — fits the budget.

The planner is deliberately strict: anything it cannot prove tileable
(carried dependences, multi-iterator or non-unit-coefficient subscripts,
scalar targets) raises ``TilingError`` and the caller falls back to the
generic vectorized lowering.  Everything it accepts is exactly the class the
paper's normalization produces for PolyBench and CLOUDSC.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Sequence

from .codegen import _ACC_INIT, Unsupported
from .dependence import EQ, nest_direction_vectors
from .ir import (
    Access,
    Affine,
    Computation,
    Loop,
    Node,
    Program,
    loop_iterators,
    nest_computations,
)

LANE = 128    # TPU lane width (last axis)
SUBLANE = 8   # fp32 sublane (second-to-last axis)

DEFAULT_VMEM_BUDGET = 1 << 23  # bytes (~8 MB of the ~16 MB/core VMEM)


class TilingError(Unsupported):
    """The nest is outside the tiled-Pallas class; fall back to vectorize."""


@dataclass(frozen=True)
class TiledIter:
    """One iterator of the nest mapped onto the grid (or kept in-tile)."""

    name: str
    start: int
    stop: int
    tile: int
    role: str  # 'parallel' | 'reduce_grid' | 'reduce_inner'

    @property
    def trip(self) -> int:
        """Iteration count of the underlying loop range."""
        return max(0, self.stop - self.start)

    @property
    def n_tiles(self) -> int:
        """Grid extent along this iterator (ceil-divided, at least 1)."""
        return max(1, -(-self.trip // self.tile))


@dataclass(frozen=True)
class DimMap:
    """How one array dimension of an access maps onto the plan.

    ``iterator`` is None for constant subscripts; ``const`` carries the
    affine constant (the stencil offset / loop-start shift folded into the
    operand view's origin by the emitter).
    """

    iterator: str | None
    const: int


@dataclass
class TilePlan:
    """Complete tiling decision for one nest: axis roles, grid, halos."""

    kind: str                         # 'parallel' | 'reduce'
    parallel: tuple[TiledIter, ...]   # loop order (outer -> inner)
    reduce_inner: tuple[TiledIter, ...]
    reduce_grid: TiledIter | None
    comps: tuple[Computation, ...]    # program order
    grid: tuple[int, ...]             # parallel tiles (+ reduction tiles last)
    vmem_bytes: int
    halo: dict[str, tuple[tuple[int, int], ...]]  # array -> per-dim (lo, hi) pad

    @property
    def axes(self) -> tuple[TiledIter, ...]:
        """Canonical slab axis order: parallel, inner reductions, grid reduction."""
        tail = (self.reduce_grid,) if self.reduce_grid is not None else ()
        return self.parallel + self.reduce_inner + tail

    @property
    def axis_of(self) -> dict[str, int]:
        """Iterator name -> position in the canonical slab axis order."""
        return {a.name: k for k, a in enumerate(self.axes)}

    @property
    def iter_of(self) -> dict[str, TiledIter]:
        """Iterator name -> its ``TiledIter``."""
        return {a.name: a for a in self.axes}

    def access_dims(self, a: Access) -> list[DimMap]:
        """Per-dimension ``DimMap`` of one access under this plan."""
        return [_dim_map(ix, self.iter_of) for ix in a.index]


def _dim_map(ix: Affine, iters: Mapping[str, TiledIter]) -> DimMap:
    its = ix.iterators()
    if not its:
        if ix.coeffs:  # non-affine marker
            raise TilingError("non-affine subscript")
        return DimMap(None, ix.const)
    if len(its) != 1 or ix.coeff(its[0]) != 1:
        raise TilingError(f"subscript {ix!r} is not a unit-coefficient iterator")
    if its[0] not in iters:
        raise TilingError(f"iterator {its[0]} not bound by the nest")
    return DimMap(its[0], ix.const)


def _loop_bounds(nest: Node) -> dict[str, tuple[int, int]]:
    out: dict[str, tuple[int, int]] = {}

    def rec(n: Node) -> None:
        if isinstance(n, Loop):
            if n.step != 1:
                raise TilingError(f"loop {n.iterator} has step {n.step}")
            out[n.iterator] = (n.start, n.stop)
            for b in n.body:
                rec(b)

    rec(nest)
    return out


def _align_units(comps: Sequence[Computation]) -> dict[str, int]:
    """Tile alignment per iterator for auto-chosen tiles: 128 where it
    subscripts the last (lane) dimension of any accessed array, 8 where it
    subscripts a second-to-last (sublane) dimension, else 1 — and 1024
    (8 x 128) for the only dimension of a rank-1 array, which Mosaic tiles
    whole.  A block dim must be a multiple of its unit or span the whole
    array dimension."""
    units: dict[str, int] = {}
    for c in comps:
        for a in (c.write,) + c.reads:
            rank = len(a.index)
            for d, ix in enumerate(a.index):
                for it in ix.iterators():
                    u = (SUBLANE * LANE if rank == 1 else LANE if d == rank - 1
                         else SUBLANE if d == rank - 2 else 1)
                    units[it] = max(units.get(it, 1), u)
    return units


def _shrink_to_budget(
    tiles: list[int],
    trips: list[int],
    units: list[int],
    block_bytes,
    budget: int,
) -> list[int]:
    """Halve the largest tile (keeping its alignment unit) until the
    estimated working set fits; stop at the floors (one unit, or the whole
    trip when that is shorter)."""
    n = len(tiles)

    def halved(k: int) -> int:
        floor = min(units[k], max(1, trips[k]))
        return max(floor, -(-(tiles[k] // 2) // units[k]) * units[k])

    while block_bytes(tiles) > budget:
        best, best_gain = -1, 0
        for k in range(n):
            gain = tiles[k] - halved(k)
            if gain > best_gain:
                best, best_gain = k, gain
        if best < 0:
            break  # at the floors everywhere: accept best effort
        tiles[best] = halved(best)
    return tiles


def plan_nest_tiling(
    program: Program,
    nest: Node,
    tile: Sequence[int] | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> TilePlan:
    """Partition a canonical nest's iterators into a Pallas grid.

    Raises ``TilingError`` for anything outside the tiled class (carried
    dependences, non-unit subscripts, scalar writes, mixed write/reduction
    roles) — callers fall back to the generic lowering.
    """
    if not isinstance(nest, Loop):
        raise TilingError("bare computation")
    bounds = _loop_bounds(nest)
    iterators = list(loop_iterators(nest))
    comps = nest_computations(nest)
    trips = {it: max(0, bounds[it][1] - bounds[it][0]) for it in iterators}
    if any(t <= 0 for t in trips.values()):
        raise TilingError("empty iteration domain")

    vectors = nest_direction_vectors(iterators, trips, comps)
    carried = [it for k, it in enumerate(iterators)
               if any(v.directions[k] != EQ for v in vectors)]
    if carried:
        raise TilingError(f"carried iterators {carried} (recurrence)")

    used = {it for c in comps for it in c.iterators()}
    if set(iterators) - used:
        raise TilingError("nest has loops no computation references")

    # Per-computation iterator roles.  Reduction axes = used but not written.
    red_its: list[str] = []
    for c in comps:
        w_its = {it for ix in c.write.index for it in ix.iterators()}
        extra = [it for it in iterators if it in set(c.iterators()) - w_its]
        if extra:
            if c.accumulate is None:
                raise TilingError(f"{c.name}: assignment under non-write axes")
            red_its.extend(it for it in extra if it not in red_its)
        if not c.write.index:
            raise TilingError(f"{c.name}: scalar write target")

    if red_its:
        if len(comps) != 1:
            raise TilingError("reduction nest with multiple computations")
        if comps[0].accumulate not in _ACC_INIT:
            raise TilingError(f"unsupported accumulate {comps[0].accumulate!r}")
        kind = "reduce"
    else:
        kind = "parallel"
    par_its = [it for it in iterators if it not in red_its]
    # An accumulate under the full parallel grid re-executes once per grid
    # step of any axis it does not use — only safe when it uses them all.
    for c in comps:
        if c.accumulate is not None and kind == "parallel":
            if set(par_its) - set(c.iterators()):
                raise TilingError(f"{c.name}: accumulate misses grid iterators")

    # ---- tile sizes -------------------------------------------------------
    red_order = [it for it in iterators if it in red_its]
    grid_red_it = red_order[-1] if red_order else None
    par_tiles = [trips[it] for it in par_its]
    red_tile = trips[grid_red_it] if grid_red_it else None
    if tile:
        want = [max(1, int(x)) for x in tile]
        if kind == "reduce" and len(want) > 1:
            red_tile = min(want.pop(), red_tile)
        want = want[-len(par_its):] if par_its else []
        for k, w in zip(range(len(par_its) - len(want), len(par_its)), want):
            par_tiles[k] = min(w, par_tiles[k])
    else:
        all_tiles = par_tiles + ([red_tile] if red_tile else [])
        all_trips = [trips[it] for it in par_its] + (
            [trips[grid_red_it]] if grid_red_it else [])

        def est(ts: list[int]) -> int:
            """VMEM estimate for a candidate tile assignment."""
            p = dict(zip(par_its + ([grid_red_it] if grid_red_it else []), ts))
            return _estimate_vmem(program, comps, p, trips, red_order)

        unit_of = _align_units(comps)
        all_units = [unit_of.get(it, 1)
                     for it in par_its + ([grid_red_it] if grid_red_it else [])]
        all_tiles = _shrink_to_budget(all_tiles, all_trips, all_units, est, vmem_budget)
        par_tiles = all_tiles[: len(par_its)]
        if grid_red_it:
            red_tile = all_tiles[-1]

    parallel = tuple(
        TiledIter(it, *bounds[it], tile=t, role="parallel")
        for it, t in zip(par_its, par_tiles)
    )
    reduce_inner = tuple(
        TiledIter(it, *bounds[it], tile=trips[it], role="reduce_inner")
        for it in red_order[:-1]
    )
    reduce_grid = (
        TiledIter(grid_red_it, *bounds[grid_red_it], tile=red_tile,
                  role="reduce_grid")
        if grid_red_it else None
    )
    grid = tuple(p.n_tiles for p in parallel)
    if reduce_grid is not None:
        grid = grid + (reduce_grid.n_tiles,)

    plan = TilePlan(
        kind=kind, parallel=parallel, reduce_inner=reduce_inner,
        reduce_grid=reduce_grid, comps=tuple(comps), grid=grid,
        vmem_bytes=0, halo={},
    )
    _validate_accesses(program, plan)
    plan.halo = _halo(program, plan)
    tile_map = {a.name: a.tile for a in plan.axes}
    plan.vmem_bytes = _estimate_vmem(program, comps, tile_map, trips, red_order)
    return plan


def _validate_accesses(program: Program, plan: TilePlan) -> None:
    writes: dict[str, tuple] = {}
    par = {a.name for a in plan.parallel}
    for c in plan.comps:
        for a in (c.write,) + c.reads:
            dims = plan.access_dims(a)  # raises on non-unit subscripts
            seen = [d.iterator for d in dims if d.iterator is not None]
            if len(seen) != len(set(seen)):
                raise TilingError(f"{a.array}: iterator used in two dims")
            if len(dims) != len(program.array(a.array).shape):
                raise TilingError(f"{a.array}: rank mismatch")
        wdims = plan.access_dims(c.write)
        if any(d.iterator is not None and d.iterator not in par for d in wdims):
            raise TilingError(f"{c.name}: write subscript uses reduction axis")
        prev = writes.get(c.write.array)
        if prev is not None and prev != c.write.index:
            raise TilingError(f"{c.write.array}: two write maps in one nest")
        writes[c.write.array] = c.write.index
        # reads of an array written earlier in the nest must match the write
        # map exactly (the emitter forwards the in-kernel slab)
        for r in c.reads:
            if r.array in writes and writes[r.array] != r.index:
                raise TilingError(f"{r.array}: read of stale in-kernel write")


def _halo(program: Program, plan: TilePlan) -> dict[str, tuple[tuple[int, int], ...]]:
    """Per array dimension, how far padded views reach outside [0, extent).

    A dimension subscripted ``it + c`` is materialized (by the emitter) as a
    view of length ``n_tiles * tile`` starting at ``start + c`` — the pad
    covers both the stencil offsets and the tile-rounding tail."""
    iters = plan.iter_of
    lo: dict[str, list[int]] = {}
    hi: dict[str, list[int]] = {}
    for c in plan.comps:
        for a in (c.write,) + c.reads:
            shape = program.array(a.array).shape
            l = lo.setdefault(a.array, [0] * len(shape))
            h = hi.setdefault(a.array, [0] * len(shape))
            for d, dm in enumerate(plan.access_dims(a)):
                if dm.iterator is None:
                    if not 0 <= dm.const < shape[d]:
                        raise TilingError(f"{a.array}: constant index OOB")
                    continue
                ti = iters[dm.iterator]
                origin = ti.start + dm.const
                span = ti.n_tiles * ti.tile
                l[d] = max(l[d], -origin)
                h[d] = max(h[d], origin + span - shape[d])
    return {k: tuple(zip(lo[k], hi[k])) for k in lo}


def _estimate_vmem(
    program: Program,
    comps: Sequence[Computation],
    tile_of: Mapping[str, int],
    trips: Mapping[str, int],
    red_order: Sequence[str],
) -> int:
    """Bytes resident in VMEM per grid step, as the TPU lays them out.

    One block per distinct access map plus the old-content alias and the
    output block of each write, each double-buffered by the Pallas
    pipeline; for a reduction also the accumulator and the in-kernel value
    over the whole slab (every axis at its tile) that is reduced into it.
    A block keeps the array's rank (a constant subscript is a dimension of
    extent 1) and is stored in (8, 128) tiles: its last dimension pads to
    128 lanes and the one before to 8 sublanes (a rank-1 block to 8 x 128),
    so a trailing extent-1 dimension costs 128 elements."""
    itemsize = 4
    inner = set(red_order[:-1])

    def block_bytes(a: Access) -> int:
        dims = []
        for ix in a.index:
            its = ix.iterators()
            if not its:
                dims.append(1)
            else:
                it = its[0]
                dims.append(trips[it] if it in inner else tile_of.get(it, trips[it]))
        if dims:
            last = SUBLANE * LANE if len(dims) == 1 else LANE
            dims[-1] = -(-dims[-1] // last) * last
        if len(dims) > 1:
            dims[-2] = -(-dims[-2] // SUBLANE) * SUBLANE
        n = itemsize
        for d in dims:
            n *= d
        return n

    seen: set[tuple] = set()
    total = 0
    for c in comps:
        for a in (c.write,) + c.reads:
            key = (a.array, a.index)
            if key in seen:
                continue
            seen.add(key)
            total += 2 * block_bytes(a)
        total += 2 * block_bytes(c.write)  # output block
        if c.accumulate is not None and red_order:
            total += block_bytes(c.write)  # accumulator scratch
            slab = itemsize
            for it in c.iterators():
                slab *= trips[it] if it in inner else tile_of.get(it, trips[it])
            total += slab
    return total
