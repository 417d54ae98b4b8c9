"""Grid-tiled Pallas lowering + scan recurrence lowering (PR-3 tentpole).

Covers: the tiling planner (tile clamping, VMEM budget, rejection of
recurrences), oracle equivalence of the interpret-mode ``pallas_nest`` /
``pallas_reduce`` paths and the ``lax.scan`` recurrence path across every
PolyBench A+B variant and both CLOUDSC programs, guard/halo edge cases, and
the search/probe memoization satellites.
"""
import numpy as np
import pytest

from repro.core import (
    Schedule,
    TilingError,
    compile_jax,
    execute_numpy,
    normalize,
    optimization_pipeline,
    plan_nest_tiling,
)
from repro.core import codegen
from repro.core.ir import (
    Array,
    Computation,
    Loop,
    Program,
    acc,
    aff,
    nest_computations,
)
from repro.core.recipes import Recipe
from repro.core.scheduler import random_inputs
from repro.core.search import schedule_from_recipe
from repro.cloudsc import erosion_program, mini_cloudsc_program
from repro.cloudsc.erosion import physical_inputs
from repro.cloudsc.scheme import scheme_inputs
from repro.kernels import nest_kernel
from repro.polybench import BENCHMARKS, NAMES

# Small tiles at mini sizes force multi-tile grids, partial tiles, and
# mask/halo handling — the interesting paths.
PALLAS = Schedule(mode="canonical", use_idioms=False, pallas_nest=True,
                  pallas_reduce=True, nest_tile=(4, 8), scan=True)
PIPE = optimization_pipeline(fuse=True)


def run_f32(program, sched, inputs):
    fn = compile_jax(program, sched)
    return fn({k: np.asarray(v, np.float32) for k, v in inputs.items()})


def max_rel(out, ref):
    denom = max(1e-9, float(np.abs(ref).max()))
    return float(np.abs(np.asarray(out, np.float64) - ref).max()) / denom


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------
class TestPlanner:
    def test_gemm_mac_is_reduce_with_clamped_tiles(self):
        p = normalize(BENCHMARKS["gemm"].make("a", "mini"))
        mac = p.body[1]
        plan = plan_nest_tiling(p, mac, tile=(8, 16, 4))
        assert plan.kind == "reduce"
        assert plan.reduce_grid is not None and plan.reduce_grid.tile == 4
        # tiles are clamped to the (mini) extents
        assert all(a.tile <= a.trip for a in plan.axes)
        assert plan.grid == tuple(a.n_tiles for a in plan.parallel) + (
            plan.reduce_grid.n_tiles,)

    def test_recurrence_rejected(self):
        p = normalize(BENCHMARKS["jacobi-2d"].make("a", "mini"))
        nest = p.body[0]  # the time-carried SCC
        with pytest.raises(TilingError):
            plan_nest_tiling(p, nest)

    def test_vmem_budget_shrinks_tiles(self):
        n = 4096
        comp = Computation("cp", acc("B", "i", "j"), (acc("A", "i", "j"),),
                           lambda v: v * 2.0)
        prog = Program("big", (Array("A", (n, n)), Array("B", (n, n))),
                       (Loop("i", n, body=(Loop("j", n, body=(comp,)),)),))
        plan = plan_nest_tiling(prog, prog.body[0], vmem_budget=1 << 20)
        assert plan.vmem_bytes <= 1 << 20
        tiles = [a.tile for a in plan.parallel]
        assert any(t < n for t in tiles)
        # auto-chosen tiles stay VPU-aligned (sublane 8 / lane 128 multiples)
        assert tiles[-1] % 128 == 0 and tiles[-2] % 8 == 0

    def test_vmem_estimate_pads_blocks_to_tpu_tiles(self):
        # a constant subscript keeps its dimension as extent 1, which the TPU
        # pads to 128 lanes; every pipelined block is double-buffered
        comp = Computation("cp", acc("B", "i", "j"), (acc("A", "i", "j", aff(const=0)),),
                           lambda v: v * 2.0)
        prog = Program("lanes", (Array("A", (64, 256, 3)), Array("B", (64, 256))),
                       (Loop("i", 64, body=(Loop("j", 256, body=(comp,)),)),))
        plan = plan_nest_tiling(prog, prog.body[0], tile=(64, 256))
        a_block = 64 * 256 * 128 * 4          # (64, 256, 1) -> (64, 256, 128)
        b_block = 64 * 256 * 4
        assert plan.vmem_bytes == 2 * a_block + 2 * 2 * b_block
        # under a budget the planner shrinks the tiles to fit
        small = plan_nest_tiling(prog, prog.body[0], vmem_budget=1 << 22)
        assert small.vmem_bytes <= 1 << 22
        assert [a.tile for a in small.parallel] != [64, 256]

    def test_reduction_slab_counts_toward_vmem(self):
        prog = normalize(BENCHMARKS["gemm"].make("b", "bench"))
        plan = plan_nest_tiling(prog, prog.body[-1])
        assert plan.kind == "reduce" and plan.vmem_bytes <= 1 << 23
        slab = np.prod([a.tile for a in plan.axes]) * 4
        assert slab < 1 << 23  # the (i, j, k) product no longer spans 320^3

    def test_rank1_tiles_align_to_whole_mosaic_tiles(self):
        # Mosaic tiles a rank-1 operand in 8 x 128 = 1024 elements: an auto
        # tile of a rank-1 array is a multiple of 1024 or the whole extent
        n = 3000
        comp = Computation("ax", acc("y", "j"), (acc("A", "i", "j"), acc("x", "i")),
                           lambda a, b: a * b, accumulate="+")
        prog = Program("atax", (Array("A", (n, n)), Array("x", (n,)), Array("y", (n,))),
                       (Loop("j", n, body=(Loop("i", n, body=(comp,)),)),))
        plan = plan_nest_tiling(prog, prog.body[0], vmem_budget=1 << 22)
        for a in plan.axes:
            assert a.tile == n or a.tile % 1024 == 0, (a.name, a.tile)

    def test_halo_covers_stencil_offsets(self):
        n = 10
        st = Computation(
            "st", acc("B", "i", "j"),
            (acc("A", aff("i", const=-1), "j"), acc("A", aff("i", const=1), "j"),
             acc("A", "i", aff("j", const=-1)), acc("A", "i", aff("j", const=1))),
            lambda a, b, c, d: 0.25 * (a + b + c + d))
        prog = Program("st", (Array("A", (n, n)), Array("B", (n, n))),
                       (Loop("i", n - 1, start=1,
                             body=(Loop("j", n - 1, start=1, body=(st,)),)),))
        plan = plan_nest_tiling(prog, prog.body[0], tile=(3, 3))
        (alo, ahi), (blo, bhi) = plan.halo["A"]
        assert alo == 0 and blo == 0  # start=1 absorbs the -1 offset
        # +1 offset plus 3x3 tile rounding (span 9 from origin 2) overhangs
        # the extent-10 dims by 1
        assert ahi == 1 and bhi == 1


# ---------------------------------------------------------------------------
# oracle equivalence: polybench A+B and CLOUDSC through pallas + scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("variant", ["a", "b"])
def test_polybench_pallas_matches_oracle(name, variant):
    b = BENCHMARKS[name]
    prog = b.make(variant, "mini")
    inp = random_inputs(prog, seed=3, dtype=np.float64)
    ref = execute_numpy(prog, inp)[b.output]
    norm = PIPE.run(prog)
    before = dict(nest_kernel.EMITTED)
    out = run_f32(norm, PALLAS, inp)[b.output]
    assert max_rel(out, ref) < 2e-4
    # parallel/reduction nests must actually lower through Pallas; only
    # time-carried stencils (jacobi/heat/fdtd) are all-recurrence programs
    emitted = sum(nest_kernel.EMITTED.values()) - sum(before.values())
    if name not in ("jacobi-2d", "heat-3d", "fdtd-2d"):
        assert emitted > 0, "no nest took the Pallas path"


@pytest.mark.parametrize("maker,inputs_fn,checks", [
    (erosion_program, physical_inputs, ("ZTP1", "ZQSMIX")),
    (mini_cloudsc_program, scheme_inputs,
     ("ZTP1", "ZQSMIX", "ZQL", "ZQI", "PFPLSL", "TENDQ")),
])
def test_cloudsc_pallas_scan_matches_oracle(maker, inputs_fn, checks):
    p = maker(8, 6)
    inp = inputs_fn(8, 6)
    ref = execute_numpy(p, inp)
    norm = PIPE.run(p)
    scans0 = codegen.LOWERING_STATS["scan"]
    out = run_f32(norm, PALLAS, inp)
    for k in checks:
        assert max_rel(out[k], ref[k]) < 1e-4, k
    # the vertical (JK-carried) chains stream through lax.scan
    assert codegen.LOWERING_STATS["scan"] > scans0


def test_mini_cloudsc_parallel_stages_take_pallas():
    p = mini_cloudsc_program(8, 6)
    norm = PIPE.run(p)
    before = dict(nest_kernel.EMITTED)
    run_f32(norm, PALLAS, scheme_inputs(8, 6))
    assert nest_kernel.EMITTED["pallas_nest"] > before["pallas_nest"]


# ---------------------------------------------------------------------------
# guard / halo edge cases
# ---------------------------------------------------------------------------
def _stencil_program(n):
    st = Computation(
        "st", acc("B", "i", "j"),
        (acc("A", "i", "j"),
         acc("A", aff("i", const=-1), "j"), acc("A", aff("i", const=1), "j"),
         acc("A", "i", aff("j", const=-1)), acc("A", "i", aff("j", const=1))),
        lambda c, nn, ss, ww, ee: c + 0.2 * (nn + ss + ww + ee))
    return Program("stencil", (Array("A", (n, n)), Array("B", (n, n))),
                   (Loop("i", n - 1, start=1,
                         body=(Loop("j", n - 1, start=1, body=(st,)),)),))


@pytest.mark.parametrize("tile", [(3, 3), (4, 8), (16, 16)])
def test_stencil_halo_partial_tiles(tile):
    prog = _stencil_program(10)
    inp = random_inputs(prog, seed=1, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    before = nest_kernel.EMITTED["pallas_nest"]
    sched = Schedule(mode="canonical", use_idioms=False, pallas_nest=True,
                     nest_tile=tile)
    out = run_f32(prog, sched, inp)
    assert nest_kernel.EMITTED["pallas_nest"] == before + 1
    assert max_rel(out["B"], ref["B"]) < 1e-6
    # untouched boundary rows keep their original content (bit-exact in f32)
    np.testing.assert_array_equal(
        np.asarray(out["B"])[0], inp["B"][0].astype(np.float32))


def test_triangular_guarded_write_partial_tiles():
    n = 11
    tri = aff("i", ("j", -1))  # j <= i
    sc = Computation("sc", acc("C", "i", "j"), (acc("C", "i", "j"),),
                     lambda c: c * 3.0, guards=(tri,))
    prog = Program("tri", (Array("C", (n, n)),),
                   (Loop("i", n, body=(Loop("j", n, body=(sc,)),)),))
    inp = random_inputs(prog, seed=2, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    sched = Schedule(mode="canonical", use_idioms=False, pallas_nest=True,
                     nest_tile=(4, 4))
    out = run_f32(prog, sched, inp)
    assert max_rel(out["C"], ref["C"]) < 1e-6  # upper triangle untouched


@pytest.mark.parametrize("unroll", [1, 2, 4])
def test_guarded_reduction_with_unroll(unroll):
    """Triangular MAC through pallas_reduce; the recipe's unroll knob splits
    the in-tile reduction into sequentially accumulated chunks."""
    n, m = 9, 16
    tri = aff("i", ("j", -1))
    mac = Computation("mac", acc("C", "i", "j"),
                      (acc("A", "i", "k"), acc("A", "j", "k")),
                      lambda a, b: a * b, accumulate="+", guards=(tri,))
    prog = Program("syrk1", (Array("A", (n, m)), Array("C", (n, n))),
                   (Loop("i", n, body=(Loop("j", n, body=(
                       Loop("k", m, body=(mac,)),)),)),))
    inp = random_inputs(prog, seed=4, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    before = nest_kernel.EMITTED["pallas_reduce"]
    sched = Schedule(mode="canonical", use_idioms=False, pallas_reduce=True,
                     nest_tile=(4, 4, 8), unroll=unroll)
    out = run_f32(prog, sched, inp)
    assert nest_kernel.EMITTED["pallas_reduce"] == before + 1
    assert max_rel(out["C"], ref["C"]) < 1e-5


def _ref_reads(jaxpr, refs) -> int:
    """How many ``get``s of ``refs`` a kernel jaxpr makes, into ``pl.when``
    branches."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "get" and any(e.invars[0] is r for r in refs):
            n += 1
        if e.primitive.name == "cond":
            for br in e.params["branches"]:
                inner = [v for v, a in zip(br.jaxpr.invars, e.invars[1:])
                         if any(a is r for r in refs)]
                n += _ref_reads(br.jaxpr, inner)
    return n


@pytest.mark.parametrize("tile", [(8, 128, 8), None])
def test_nest_kernels_never_read_their_output_blocks(tile):
    """On a TPU an output block is never loaded from HBM, so a kernel that
    reads it sees what an earlier block left in VMEM (interpret mode hides
    this: it starts outputs from the aliased inputs).  Old content must
    come from the aliased input block."""
    import jax

    prog = normalize(BENCHMARKS["correlation"].make("a", "mini"))
    fn = compile_jax(prog, Schedule(mode="canonical", use_idioms=False, pallas_nest=True,
                                    pallas_reduce=True, nest_tile=tile))
    jaxpr = jax.make_jaxpr(fn)(random_inputs(prog, seed=0))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert any(e.params["jaxpr"].eqns and len(e.outvars) for e in calls)
    for e in calls:
        kernel = e.params["jaxpr"]
        outs = kernel.invars[len(e.invars):len(e.invars) + len(e.outvars)]
        assert _ref_reads(kernel, outs) == 0


def test_unroll_flows_from_recipe_to_schedule():
    sched = schedule_from_recipe(Recipe(kind="pallas_reduce", tile=(8, 128, 128),
                                        unroll=4))
    assert sched.pallas_reduce and sched.unroll == 4 and sched.nest_tile == (8, 128, 128)
    sched = schedule_from_recipe(Recipe(kind="pallas_nest", tile=(8, 128)))
    assert sched.pallas_nest and sched.nest_tile == (8, 128)


# ---------------------------------------------------------------------------
# scan recurrence lowering
# ---------------------------------------------------------------------------
def _recurrence_program(n, rows, lookback=1):
    reads = [acc("X", "t", "j")] + [
        acc("F", aff("t", const=-d), "j") for d in range(1, lookback + 1)]
    weights = [0.5 / d for d in range(1, lookback + 1)]
    comp = Computation(
        "rec", acc("F", "t", "j"), tuple(reads),
        lambda x, *fs: x + sum(w * f for w, f in zip(weights, fs)))
    return Program("rec", (Array("X", (n, rows)), Array("F", (n, rows))),
                   (Loop("t", n, body=(Loop("j", rows, body=(comp,)),)),),
                   temps=("F",))


@pytest.mark.parametrize("lookback", [1, 2])
def test_scan_recurrence_matches_oracle(lookback):
    prog = _recurrence_program(7, 5, lookback)
    inp = random_inputs(prog, seed=5, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    scans0 = codegen.LOWERING_STATS["scan"]
    out = run_f32(prog, Schedule(mode="canonical", use_idioms=False), inp)
    assert codegen.LOWERING_STATS["scan"] == scans0 + 1
    assert max_rel(out["F"], ref["F"]) < 1e-6


def test_scan_disabled_falls_back_to_fori():
    prog = _recurrence_program(7, 5)
    inp = random_inputs(prog, seed=5, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    fori0 = codegen.LOWERING_STATS["fori"]
    out = run_f32(prog, Schedule(mode="canonical", use_idioms=False,
                                 scan=False), inp)
    assert codegen.LOWERING_STATS["fori"] > fori0
    assert max_rel(out["F"], ref["F"]) < 1e-6


def test_scan_guarded_first_row():
    """CLOUDSC-flux shape: guarded init at t==0, lookback elsewhere."""
    n, rows = 6, 4
    pfl = Computation("pfl", acc("F", "t", "j"),
                      (acc("F", aff("t", const=-1), "j"), acc("X", "t", "j")),
                      lambda f, x: 0.8 * f + x,
                      guards=(aff("t", const=-1),))           # t >= 1
    pfl0 = Computation("pfl0", acc("F", "t", "j"), (acc("X", "t", "j"),),
                       lambda x: x, guards=(aff(("t", -1)),))  # t == 0
    prog = Program("flux", (Array("X", (n, rows)), Array("F", (n, rows))),
                   (Loop("t", n, body=(Loop("j", rows, body=(pfl, pfl0)),)),),
                   temps=("F",))
    inp = random_inputs(prog, seed=6, dtype=np.float64)
    ref = execute_numpy(prog, inp)
    scans0 = codegen.LOWERING_STATS["scan"]
    out = run_f32(prog, Schedule(mode="canonical", use_idioms=False), inp)
    assert codegen.LOWERING_STATS["scan"] == scans0 + 1
    assert max_rel(out["F"], ref["F"]) < 1e-6


# ---------------------------------------------------------------------------
# scheduler plumbing: pallas recipes through Daisy + backend selection
# ---------------------------------------------------------------------------
def test_daisy_compiles_pallas_recipes_from_db():
    from repro.core import Daisy, TuningDatabase, fingerprint
    from repro.core.embedding import embed_nest

    b = BENCHMARKS["gemm"]
    prog = b.make("a", "mini")
    db = TuningDatabase()
    d = Daisy(db=db, backend="pallas_interpret")
    norm = d.plan(prog).program
    for nest in norm.body:
        kind = ("pallas_reduce"
                if any(c.accumulate for c in nest_computations(nest))
                else "pallas_nest")
        db.add(fingerprint(nest), embed_nest(norm, nest),
               Recipe(kind=kind, tile=(4, 8, 8)), provenance="test")
    before = dict(nest_kernel.EMITTED)
    fn, plan = d.compile(prog, jit=False)
    assert all(p.recipe.kind.startswith("pallas") for p in plan.nests)
    inp = random_inputs(prog, seed=8, dtype=np.float64)
    ref = execute_numpy(prog, inp)[b.output]
    out = fn({k: np.asarray(v, np.float32) for k, v in inp.items()})[b.output]
    assert max_rel(out, ref) < 2e-4
    assert sum(nest_kernel.EMITTED.values()) > sum(before.values())


def test_daisy_backend_xla_degrades_pallas_kinds():
    from repro.core import Daisy

    d = Daisy(backend="xla")
    assert d._backend_recipe(Recipe(kind="pallas_nest", tile=(8, 128))).kind == "vectorize"
    assert d._backend_recipe(Recipe(kind="pallas_reduce")).kind == "vectorize"
    assert d._backend_recipe(Recipe(kind="pallas_gemm")).kind == "einsum"
    assert d._backend_recipe(Recipe(kind="einsum")).kind == "einsum"
    assert Daisy(backend="pallas").interpret is False
    with pytest.raises(ValueError):
        Daisy(backend="tpu")


# ---------------------------------------------------------------------------
# satellites: memoization
# ---------------------------------------------------------------------------
def test_evolve_recipe_measures_each_candidate_once(monkeypatch):
    from repro.core import search

    calls = []
    monkeypatch.setattr(
        search, "measure_recipe",
        lambda prog, inputs, r, repeats=3, interpret=True: calls.append(r) or 1.0)
    prog = normalize(BENCHMARKS["gemm"].make("a", "mini"))
    from repro.core.scheduler import nest_program

    nprog = nest_program(prog, prog.body[0])
    inp = random_inputs(nprog)
    search.evolve_recipe(nprog, inp, Recipe(kind="vectorize"),
                         iterations=3, population=4)
    assert len(calls) == len(set(calls)), "a recipe was re-measured"


def test_is_multiplicative_probe_memoized(monkeypatch):
    probes = [0]
    real = codegen._is_multiplicative_probe

    def counting(expr, n_reads):
        probes[0] += 1
        return real(expr, n_reads)

    monkeypatch.setattr(codegen, "_is_multiplicative_probe", counting)
    f = lambda a, b: a * b  # noqa: E731
    assert codegen._is_multiplicative(f, 2) == 1.0
    assert codegen._is_multiplicative(f, 2) == 1.0
    assert probes[0] == 1
