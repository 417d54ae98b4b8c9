"""Guard-aware vectorization legality: ``dependence.guarded_disjoint`` and
its one user, ``_NestEmitter.plan`` in canonical mode.

correlation's and covariance's symmetrisation ``corr[k6,k5] = corr[k5,k6]
if k6 > k5`` writes the strict lower triangle and reads the strict upper
one, so it carries no dependence and vectorizes to one masked transpose.
Every other nest of the PolyBench B programs and of CLOUDSC keeps the plan
it had without the guard test, and ``as_written`` never consults guards.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cloudsc import erosion_program, mini_cloudsc_program
from repro.core import Daisy, Schedule, codegen, compile_jax, execute_numpy, spans
from repro.core.codegen import _NestEmitter
from repro.core.dependence import guarded_disjoint, nest_direction_vectors
from repro.core.ir import (NONAFFINE, Access, Affine, Computation, Loop, Program, Read, acc,
                           aff, walk)
from repro.polybench import BENCHMARKS

SYM_PROGRAMS = ("correlation", "covariance")


def mini(name: str, variant: str = "b") -> Program:
    return BENCHMARKS[name].variants[variant](BENCHMARKS[name].sizes["mini"])


def placed(nest) -> dict[str, tuple]:
    """computation name -> (enclosing loops, computation)."""
    return {c.name: (loops, c) for loops, c in walk(nest)}


def copy_nest(m: int, guards=()) -> Loop:
    """``T[k6,k5] = T[k5,k6]`` over an m x m box under ``guards``."""
    c = Computation("cp", acc("T", "k6", "k5"), (acc("T", "k5", "k6"),), Read(0),
                    guards=tuple(guards))
    return Loop("k5", m, body=(Loop("k6", m, body=(c,)),))


def write_read(nest, name: str):
    loops, c = placed(nest)[name]
    return (loops, c, c.write), (loops, c, c.reads[0])


def plan_without_guards(program: Program, nest, schedule: Schedule, monkeypatch):
    """The plan as it was before the guard test: direction vectors only."""
    with monkeypatch.context() as mp:
        mp.setattr(codegen, "nest_direction_vectors",
                   lambda its, trip, comps, loops=None: nest_direction_vectors(its, trip, comps))
        return _NestEmitter(program, schedule).plan(nest)


def sym_nests(program: Program) -> set[int]:
    return {i for i, n in enumerate(program.body)
            if any(c.name == "sym" for _, c in walk(n))}


def daisy_program(program: Program) -> Program:
    return Daisy().plan(program).program


# ---------------------------------------------------------------------------
# guarded_disjoint
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", SYM_PROGRAMS)
@pytest.mark.parametrize("size", ["mini", "bench"])
def test_sym_pair_is_disjoint(name, size):
    p = BENCHMARKS[name].variants["b"](BENCHMARKS[name].sizes[size])
    w, r = write_read(p.body[-1], "sym")
    assert guarded_disjoint(*w, *r) and guarded_disjoint(*r, *w)
    # the write against itself is the same element at the same point
    assert not guarded_disjoint(*w, *w)


def test_unguarded_transpose_copy_overlaps():
    w, r = write_read(copy_nest(8), "cp")
    assert not guarded_disjoint(*w, *r)


def test_overlapping_band_overlaps():
    # k6 > k5 - 2 writes and reads the band |k6 - k5| <= 1 on both sides
    w, r = write_read(copy_nest(8, [aff("k6", ("k5", -1), const=1)]), "cp")
    assert not guarded_disjoint(*w, *r)
    # the strict triangle k6 > k5 is disjoint; so is k6 >= k5 + 2
    for const in (-1, -2):
        w, r = write_read(copy_nest(8, [aff("k6", ("k5", -1), const=const)]), "cp")
        assert guarded_disjoint(*w, *r), const


def test_cloudsc_pfl_recurrence_overlaps():
    p = mini_cloudsc_program(nproma=8, klev=4)
    nest = next(n for n in p.body if "pfl" in placed(n))
    by_name = placed(nest)
    loops, pfl = by_name["pfl"]
    loops0, pfl0 = by_name["pfl0"]
    lag = pfl.reads[0]  # PFPLSL[JK-1, JL], under JK >= 1
    assert lag.index[0].const == -1
    # JK = 0 writes what JK = 1 reads; JK writes what JK + 1 reads
    assert not guarded_disjoint(loops0, pfl0, pfl0.write, loops, pfl, lag)
    assert not guarded_disjoint(loops, pfl, pfl.write, loops, pfl, lag)
    # the JK == 0 and JK >= 1 writes hit the same array, never the same row
    assert guarded_disjoint(loops0, pfl0, pfl0.write, loops, pfl, pfl.write)


def test_nonaffine_subscript_is_undecided():
    c = Computation("nz", acc("T", "k6", "k5"),
                    (Access("T", (Affine.of("k5", (NONAFFINE, 1)), aff("k6"))),), Read(0),
                    guards=(aff("k6", ("k5", -1), const=-1),))
    nest = Loop("k5", 8, body=(Loop("k6", 8, body=(c,)),))
    w, r = write_read(nest, "nz")
    assert not guarded_disjoint(*w, *r)


def test_rank_mismatch_and_other_arrays():
    nest = copy_nest(4, [aff("k6", ("k5", -1), const=-1)])
    (loops, c, w), _ = write_read(nest, "cp")
    assert not guarded_disjoint(loops, c, w, loops, c, Access("T", (aff("k5"),)))
    assert guarded_disjoint(loops, c, w, loops, c, acc("U", "k5", "k6"))


def test_empty_and_strided_domains():
    c = Computation("cp", acc("T", "k6", "k5"), (acc("T", "k5", "k6"),), Read(0))
    empty = (Loop("k5", 4, start=4), Loop("k6", 8))
    full = (Loop("k5", 8), Loop("k6", 8))
    assert guarded_disjoint(empty, c, c.write, full, c, c.reads[0])  # no points: no overlap
    # a strided loop is taken as its interval, a superset of its points
    strided = (Loop("k5", 8, step=2), Loop("k6", 8, step=2))
    assert not guarded_disjoint(strided, c, c.write, strided, c, c.reads[0])  # the diagonal
    tri = Computation("tri", c.write, c.reads, Read(0), guards=(aff("k6", ("k5", -1), const=-1),))
    assert guarded_disjoint(strided, tri, tri.write, strided, tri, tri.reads[0])


@pytest.mark.parametrize("seed", range(4))
def test_disjoint_verdicts_agree_with_enumeration(seed):
    """Soundness on random small 2-D accesses and guards: whenever the test
    says disjoint, no pair of points addresses the same element."""
    rng = np.random.default_rng(seed)
    proved = 0
    for _ in range(60):
        loops = (Loop("i", int(rng.integers(1, 6))), Loop("j", int(rng.integers(1, 6))))

        def rand_aff() -> Affine:
            return Affine.of(("i", int(rng.integers(-2, 3))), ("j", int(rng.integers(-2, 3))),
                             const=int(rng.integers(-3, 4)))

        comps = [Computation(f"c{k}", Access("T", (rand_aff(), rand_aff())), (), Read(0),
                             guards=tuple(rand_aff() for _ in range(int(rng.integers(0, 3)))))
                 for k in range(2)]

        def elements(c: Computation) -> set:
            out = set()
            for i, j in itertools.product(range(loops[0].stop), range(loops[1].stop)):
                env = {"i": i, "j": j}
                ev = lambda a: a.const + sum(v * env[it] for it, v in a.coeffs)  # noqa: E731
                if all(ev(g) >= 0 for g in c.guards):
                    out.add(tuple(ev(ix) for ix in c.write.index))
            return out

        a, b = comps
        if guarded_disjoint(loops, a, a.write, loops, b, b.write):
            proved += 1
            assert not (elements(a) & elements(b)), (a, b)
    assert proved  # the cases exercise the True branch


# ---------------------------------------------------------------------------
# the vectorization plan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", SYM_PROGRAMS)
def test_sym_nest_plans_vectorized(name, monkeypatch):
    p = mini(name)
    em = _NestEmitter(p, Schedule())
    assert em.plan(p.body[-1]) == {"k5": True, "k6": True}
    assert em.guard_disjoint == 2
    assert plan_without_guards(p, p.body[-1], Schedule(), monkeypatch) == {
        "k5": False, "k6": False}


@pytest.mark.parametrize("name", SYM_PROGRAMS)
def test_sym_programs_match_oracle(name):
    p = mini(name)
    rng = np.random.default_rng(3)
    inp = {a.name: rng.uniform(0, 1, a.shape).astype(np.float32) for a in p.input_arrays}
    ref = execute_numpy(p, inp)
    fn, plan = Daisy().compile(p)
    out = fn(inp)
    o = BENCHMARKS[name].output
    np.testing.assert_allclose(np.asarray(out[o], np.float64), ref[o], rtol=2e-5, atol=1e-6)

    # the symmetrisation alone is a copy: exact
    only = Program(f"{name}_sym", p.arrays, (p.body[-1],))
    m = p.array(o).shape[0]
    x = {a.name: rng.uniform(0, 1, a.shape).astype(np.float32) for a in only.input_arrays}
    got = np.asarray(jax.jit(compile_jax(only, Schedule()))(x)[o])
    want = execute_numpy(only, x)[o]
    assert np.array_equal(got.astype(np.float64), want)
    lower = np.tril_indices(m, -1)
    assert np.array_equal(got[lower], x[o].T[lower])


PLAN_CASES = ([(n, lambda n=n: mini(n)) for n in BENCHMARKS]
              + [("cloudsc_erosion", lambda: erosion_program(nproma=8, klev=4)),
                 ("mini_cloudsc", lambda: mini_cloudsc_program(nproma=8, klev=4))])


@pytest.mark.parametrize("name,build", PLAN_CASES, ids=[n for n, _ in PLAN_CASES])
def test_guard_test_changes_only_the_sym_nests(name, build, monkeypatch):
    program = daisy_program(build())
    changed = set()
    for i, nest in enumerate(program.body):
        em = _NestEmitter(program, Schedule())
        with_guards = em.plan(nest)
        without = plan_without_guards(program, nest, Schedule(), monkeypatch)
        if with_guards != without:
            changed.add(i)
            assert em.guard_disjoint == sum(with_guards.values()) - sum(without.values())
        else:
            assert em.guard_disjoint == 0
    assert changed == (sym_nests(program) if name in SYM_PROGRAMS else set())
    assert len(changed) == (name in SYM_PROGRAMS)


@pytest.mark.parametrize("name", SYM_PROGRAMS + ("syrk", "gemm"))
def test_as_written_plans_unchanged(name, monkeypatch):
    p = mini(name, "a")
    sched = Schedule(mode="as_written")
    for nest in p.body:
        em = _NestEmitter(p, sched)
        assert em.plan(nest) == plan_without_guards(p, nest, sched, monkeypatch)
        assert em.guard_disjoint == 0


# ---------------------------------------------------------------------------
# the span attribute, the counter and the lowering taken
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", SYM_PROGRAMS)
def test_codegen_nest_span_carries_guard_disjoint(name):
    program = daisy_program(mini(name))
    (sym,) = sym_nests(program)
    before = codegen.LOWERING_STATS["guard_disjoint"]
    spans.reset()
    jax.make_jaxpr(compile_jax(program, Schedule()))(
        {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in program.input_arrays})
    nests = [s for s in spans.records() if s.name == "codegen.nest"]
    assert [s.attrs["index"] for s in nests] == list(range(len(program.body)))
    assert [s.attrs["guard_disjoint"] for s in nests] == [
        2 if i == sym else 0 for i in range(len(program.body))]
    assert nests[sym].attrs["lowering"] == "vectorize"
    assert codegen.LOWERING_STATS["guard_disjoint"] == before + 2


def test_cloudsc_spans_read_zero():
    program = daisy_program(mini_cloudsc_program(nproma=8, klev=4))
    spans.reset()
    jax.make_jaxpr(compile_jax(program, Schedule()))(
        {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in program.input_arrays})
    nests = [s for s in spans.records() if s.name == "codegen.nest"]
    assert nests and all(s.attrs["guard_disjoint"] == 0 for s in nests)
