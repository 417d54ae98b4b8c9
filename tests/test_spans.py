"""Spans of the compile path (``repro.core.spans``) and the names Daisy's
generated code carries onto the device: the span tree of one compile, pass
spans against ``Daisy.explain``, a module and a ``nest<i>/<lowering>`` scope
on the ops of every program of the benchmark's configurations, the
persistent compile cache's hits and misses, and the buffer's bound."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cloudsc import erosion_program, mini_cloudsc_program
from repro.core import Daisy, Schedule, TuningDatabase, compile_jax, normalize, spans
from repro.core.codegen import _emit_top_nest
from repro.core.database import default_pretuned_path
from repro.core.search import schedule_from_recipe
from repro.polybench import BENCHMARKS

ROOT = Path(__file__).resolve().parents[1]
LOWERINGS = {"einsum", "vectorize", "scan", "fori", "pallas_nest", "pallas_reduce",
             "pallas_gemm"}


def mini(name: str, variant: str = "b"):
    return BENCHMARKS[name].variants[variant](BENCHMARKS[name].sizes["mini"])


def ones(program) -> dict:
    return {a.name: np.ones(a.shape, np.float32) for a in program.input_arrays}


def abstract(program) -> dict:
    return {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in program.input_arrays}


def children(recs) -> dict:
    out: dict = {}
    for s in recs:
        out.setdefault(s.parent, []).append(s)
    return out


@pytest.mark.parametrize("program", [mini("gemm"), mini_cloudsc_program(nproma=64, klev=8)],
                         ids=["gemm_b", "mini_cloudsc"])
def test_span_tree_of_one_compile(program):
    daisy = Daisy()
    spans.reset()
    fn, plan = daisy.compile(program)
    jax.block_until_ready(fn(ones(program)))
    recs = spans.records()
    assert all(s.end_ns is not None and s.end_ns >= s.start_ns for s in recs)
    kids = children(recs)

    (root,) = [s for s in recs if s.name == "daisy.compile"]
    assert root.attrs == {"program": program.name, "cached": False}
    assert [s.name for s in kids[root.id]] == ["daisy.pipeline", "daisy.plan"]
    pipe, planned = kids[root.id]
    assert [s.name for s in kids[pipe.id]] == [f"pass:{n}" for n in daisy.pipeline.names]
    assert planned.attrs["nests"] == len(plan.nests)
    assert planned.attrs["from_db"] == sum(not n.source.startswith("default")
                                           for n in plan.nests)
    inner = [s for s in recs if root.start_ns <= s.start_ns and s.end_ns <= root.end_ns]
    assert len(inner) == 3 + len(daisy.pipeline.names)

    # the first call traces (where the code generator runs), lowers, compiles
    module = spans.module_name(program.name)
    (trace,) = [s for s in recs if s.name == "jax.trace"]
    assert trace.attrs == {"module": module}
    (emit,) = kids[trace.id]
    assert emit.name == "codegen.emit" and emit.attrs == {"program": program.name}
    nests = kids[emit.id]
    assert [(s.name, s.attrs["index"]) for s in nests] == [
        ("codegen.nest", i) for i in range(len(plan.program.body))]
    for s in nests:
        assert s.attrs["lowering"] and set(s.attrs["lowering"].split("+")) <= LOWERINGS
    assert [s.attrs["module"] for s in recs if s.name in ("jax.lower", "xla.compile")] == [
        module, module]

    # a memo hit is one short span, and calls record nothing
    spans.reset()
    assert daisy.compile(program) == (fn, plan)
    jax.block_until_ready(fn(ones(program)))
    (hit,) = spans.records()
    assert hit.name == "daisy.compile" and hit.attrs["cached"] is True


def test_pass_spans_carry_the_ir_sizes_and_time_of_explain():
    daisy = Daisy()
    program = mini_cloudsc_program(nproma=64, klev=8)
    spans.reset()
    ctx = daisy.explain(program)
    recs = spans.records()
    (pipe,) = [s for s in recs if s.name == "daisy.pipeline"]
    assert pipe.parent is None  # outside any daisy.compile
    passes = [s for s in recs if s.parent == pipe.id]
    assert [s.name for s in passes] == [f"pass:{r.name}" for r in ctx.records]
    for s, r in zip(passes, ctx.records):
        assert s.attrs == {"nests_before": r.nests_before, "nests_after": r.nests_after,
                           "comps_before": r.comps_before, "comps_after": r.comps_after,
                           "cached": r.cached}
        assert s.seconds == r.seconds  # one timer feeds both


def _config_programs():
    poly = json.loads((ROOT / "bench/configs/polybench-xl.json").read_text())["programs"]
    progs = [mini(name) for name in poly]
    return progs + [erosion_program(nproma=64, klev=8), mini_cloudsc_program(nproma=64, klev=8)]


@pytest.fixture(scope="module")
def config_daisy():
    return Daisy(db=TuningDatabase.load(default_pretuned_path("xla")))


def _nest_scopes(names, module: str) -> dict[int, set[str]]:
    """Nest index -> the lowering scopes right below it, over op names."""
    out: dict[int, set[str]] = {}
    for n in names:
        m = re.match(rf"jit\({module}\)/nest(\d+)/([^/]+)/", n)
        if m:
            out.setdefault(int(m.group(1)), set()).add(m.group(2))
    return out


def _emits_ops(daisy, plan, i: int) -> bool:
    """Whether canonical nest ``i`` of ``plan`` lowers to any operation."""
    sched = schedule_from_recipe(daisy._backend_recipe(plan.nests[i].recipe), daisy.interpret)
    prog = plan.program
    env = {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in prog.arrays}
    jaxpr = jax.make_jaxpr(lambda e: _emit_top_nest(prog, i, prog.body[i], sched, e))(env)
    return len(jaxpr.jaxpr.eqns) > 0


@pytest.mark.parametrize("program", _config_programs(), ids=lambda p: p.name)
def test_every_nest_carries_its_module_nest_and_lowering(program, config_daisy):
    fn, plan = config_daisy.compile(program)
    module = spans.module_name(program.name)
    lowered = fn.lower(abstract(program))
    # every canonical nest's ops, as emitted (a nest that only copies, as
    # doitgen's write-back does, emits none)
    emitted = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    scopes = _nest_scopes(emitted, module)
    with_ops = [i for i, n in enumerate(plan.nests) if _emits_ops(config_daisy, plan, i)]
    assert sorted(scopes) == with_ops and len(with_ops) >= len(plan.nests) - 1
    assert set().union(*scopes.values()) <= LOWERINGS
    # and on the compiled module (XLA folds some nests away, e.g. zero fills)
    compiled = lowered.compile().as_text()
    assert f"jit_{module}" in compiled
    op_names = set(re.findall(r'op_name="([^"]*)"', compiled))
    nested = {n for n in op_names if "/nest" in n}
    assert nested and all(re.match(rf"jit\({module}\)/nest\d+/({'|'.join(LOWERINGS)})/", n)
                          for n in nested)


@pytest.mark.parametrize("name, sched, kinds", [
    ("gemver", Schedule(pallas_nest=True, pallas_reduce=True), {"pallas_nest"}),
    ("atax", Schedule(pallas_nest=True, pallas_reduce=True), {"pallas_reduce"}),
    ("gemm", Schedule(pallas_gemm=True), {"pallas_gemm"}),
])
def test_pallas_lowerings_carry_their_scope(name, sched, kinds):
    program = normalize(mini(name, "a"))
    fn = compile_jax(program, sched)
    module = spans.module_name(program.name)
    assert fn.__name__ == module
    text = jax.jit(fn).lower(abstract(program)).as_text(debug_info=True)
    found = set().union(*_nest_scopes(set(re.findall(r'loc\("([^"]*)"', text)), module).values())
    assert kinds <= found


_SHARDED = """
import json, re
import jax, jax.numpy as jnp
from repro.cloudsc import compile_scheme, mini_cloudsc_program
fn, part = compile_scheme(nproma=64, klev=8, mesh=jax.make_mesh((2,), ("data",)))
p = mini_cloudsc_program(64, 8)
args = {a.name: jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in p.input_arrays}
text = fn.lower(args).compile().as_text()
print(part.sharded, re.findall(r"HloModule (\\w+)", text)[0])
print(json.dumps(sorted(set(
    re.findall(r'op_name="jit\\(daisy_mini_cloudsc\\)/shard_map/(nest\\d+/\\w+)/', text)))))
"""


def test_sharded_program_carries_its_module_and_nest_scopes():
    """``compile_sharded`` (two host devices, in a process of its own) names
    its function and scopes its nests as ``compile_jax`` does."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", _SHARDED], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    head, scopes = r.stdout.strip().splitlines()[-2:]
    assert head.split() == ["True", "jit_daisy_mini_cloudsc"]
    scopes = [s.split("/") for s in json.loads(scopes)]
    nests = {n for n, _ in scopes}
    assert nests == {f"nest{i}" for i in range(len(nests))} and len(nests) >= 2
    assert {k for _, k in scopes} <= LOWERINGS


def test_module_names():
    assert spans.module_name("heat-3d") == "daisy_heat_3d"
    assert spans.daisy_module("jit(daisy_heat_3d)") == "daisy_heat_3d"
    assert spans.daisy_module("daisy_heat_3d") == "daisy_heat_3d"
    assert spans.daisy_module("jit(gen)") is None
    assert spans.daisy_module("_einsum") is None


def test_jits_not_compiled_by_daisy_are_not_counted():
    spans.reset()

    def gen(k):
        return jax.random.uniform(k, (16,)) * 3.0

    jax.block_until_ready(jax.jit(gen)(jax.random.PRNGKey(0)))
    jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) + 1.0)(jnp.ones(8)))
    assert spans.records() == []


@pytest.fixture
def cache_config(request):
    """Sets JAX's compile-cache options for one test and restores them
    afterwards (xdist workers run many tests in one process)."""
    from jax._src import compilation_cache

    before = {}

    def restore():
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

    def update(**values):
        for k, v in values.items():
            before.setdefault(k, getattr(jax.config, k))
            jax.config.update(k, v)
        compilation_cache.reset_cache()  # take the directory up afresh

    request.addfinalizer(restore)
    return update


def test_compile_cache_miss_then_hit(cache_config, tmp_path):
    cache_config(jax_compilation_cache_dir=str(tmp_path),
                 jax_persistent_cache_min_compile_time_secs=0.0,
                 jax_persistent_cache_min_entry_size_bytes=0)
    program = mini("atax")
    fn, _ = Daisy().compile(program)
    outcomes = []
    for _ in range(2):
        spans.reset()
        jax.block_until_ready(fn(ones(program)))
        outcomes += [s.attrs["cache"] for s in spans.records() if s.name == "xla.compile"]
        jax.clear_caches()  # the next call traces and lowers again, and loads
    assert outcomes == ["miss", "hit"]


def test_compile_without_a_cache_directory_is_off(cache_config):
    cache_config(jax_compilation_cache_dir=None)
    program = mini("bicg")
    fn, _ = Daisy().compile(program)
    spans.reset()
    jax.block_until_ready(fn(ones(program)))
    assert [s.attrs["cache"] for s in spans.records() if s.name == "xla.compile"] == ["off"]


def test_spans_nest_and_close_on_errors():
    spans.reset()
    with pytest.raises(ValueError):
        with spans.span("outer", a=1) as outer:
            with spans.span("inner") as inner:
                raise ValueError
    with spans.span("after") as after:
        pass
    assert spans.records() == [outer, inner, after]
    assert inner.parent == outer.id and outer.parent is None and after.parent is None
    assert outer.end_ns >= inner.end_ns >= inner.start_ns >= outer.start_ns


def test_buffer_keeps_the_newest_records_up_to_its_bound():
    spans.reset()
    n = spans.MAX_RECORDS + 10
    for i in range(n):
        with spans.span("s", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.MAX_RECORDS
    assert [recs[0].attrs["i"], recs[-1].attrs["i"]] == [10, n - 1]
    spans.reset()
    assert spans.records() == []
