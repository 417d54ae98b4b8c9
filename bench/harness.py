"""One run of one benchmark cell: set-up, the measured window, the traced
round, the check against the plain reference, and the result line.

Everything a cell needs is found by name, so a later change adds a cell,
a configuration, a traffic mix or a metric by adding files:

* ``BENCHMARK.json`` names the cell's configuration and traffic;
* ``bench/configs/<config>.json`` holds the programs, their sizes, input
  ranges, operation counts of opaque calls and the correctness limits; a
  configuration with a ``builder`` of its own (``serving``) is run by
  ``bench/<builder>.py`` instead of the window below;
* ``bench/references/<config>.py`` is the plain reference of its programs;
* ``bench/traffic/<traffic>.json`` holds the variant and how the window is
  divided;
* ``bench/metrics/<metric>.py`` reads one metric from the run's record.

The window drives the jitted callable that ``Daisy.compile`` returns for
each program, with the shipped transfer-tuning database and the platform's
backend, on device arrays made from the seed.  It runs in rounds of about
the traffic's ``round_s`` seconds; in each round every program gets a
slice of the round (``equal_slices``: an equal share of wall time, filled
with as many calls as the estimate says fit, dispatched back to back with
one ``block_until_ready``; ``steps``: one call of each program in turn,
each blocked, until the round's time is used).  A program's time is the
wall time of its slices over their calls.  The traced run traces one more
round of the same length.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT_DIR = BENCH / "out"
CACHE_DIR = ROOT / ".jax_cache"


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------
def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (its name may hold ``-``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    reference: Callable[[str, dict, dict], dict]
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, Callable[[dict], float | None]] = field(default_factory=dict)
    # jitted input generators and references, built once per program and type
    jits: dict = field(default_factory=dict)


def metric_applies(metric: dict, cell_name: str, cell_e2e: set[str]) -> bool:
    """A per-layer metric runs in the cells it lists, or else in every cell
    that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric["moves"] in cell_e2e


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve cell ``name`` of ``root/BENCHMARK.json`` into a ``Cell``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({sorted(cells)})")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    return make_cell(spec, name, w["config"], cfg_file, w["traffic"], w["chips"], root)


def make_cell(spec: dict, name: str, config: str, config_file: str, traffic: str,
              chips: int = 1, root: Path = ROOT) -> Cell:
    """A ``Cell`` of configuration ``config`` under traffic ``traffic``, with
    the metrics of ``spec`` (a loaded ``BENCHMARK.json``) that apply to it."""
    ref = load_module(root / "bench" / "references" / f"{config}.py",
                      f"bench_reference_{config.replace('-', '_')}")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if metric_applies(m, name, names)]
    cell = Cell(name, chips, load_json(root / config_file),
                load_json(root / "bench" / "traffic" / f"{traffic}.json"),
                ref.reference, e2e, per_layer)
    builder = cell.config.get("builder")
    if builder is not None and not (root / "bench" / f"{builder}.py").is_file():
        raise FileNotFoundError(f"{config}: no bench/{builder}.py for builder {builder!r}")
    for m in e2e + per_layer:
        mod = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_').replace('-', '_')}")
        cell.readers[m["name"]] = mod.read
    return cell


# ---------------------------------------------------------------------------
# programs and their inputs
# ---------------------------------------------------------------------------
@dataclass
class Prog:
    """One program of a cell: the source IR, its sizes and its work."""

    name: str
    program: Any
    sizes: dict
    flops: int = 0
    nbytes: int = 0
    outputs: list[str] = field(default_factory=list)


def polybench_sizes(bench, sizes: dict, keys: dict) -> dict:
    """The suite's size dict for one kernel, from sizes named as in the
    PolyBench header.  Raises unless ``keys`` maps every header macro to
    exactly the keys the suite's builder takes."""
    if set(keys) != set(sizes):
        raise ValueError(f"{bench.name}: sizes {sorted(sizes)} vs macros {sorted(keys)}")
    suite_keys = set(bench.sizes["mini"])
    if set(keys.values()) != suite_keys:
        raise ValueError(f"{bench.name}: macros map to {sorted(keys.values())}, "
                         f"the builder takes {sorted(suite_keys)}")
    return {keys[m]: int(v) for m, v in sizes.items()}


def build_programs(config: dict, traffic: dict) -> list[Prog]:
    """The cell's source programs, in the configuration's order."""
    from . import counts

    out = []
    variant = traffic.get("variant")
    for name, entry in config["programs"].items():
        kind = entry["builder"]
        if kind == "polybench":
            from repro.polybench import BENCHMARKS

            bench = BENCHMARKS[name]
            prog = bench.variants[variant](
                polybench_sizes(bench, entry["sizes"], entry["suite_keys"]))
        elif kind == "function":
            if variant is not None:
                raise ValueError(f"{name}: a function-built program has no variant {variant!r}")
            mod_name, fn_name = entry["function"].split(":")
            prog = getattr(importlib.import_module(mod_name), fn_name)(**entry["sizes"])
        else:
            raise ValueError(f"{name}: unknown builder {kind!r}")
        p = Prog(name, prog, entry["sizes"])
        p.flops = counts.program_flops(prog, config.get("call_flops", {}))
        p.nbytes = counts.program_bytes(prog)
        p.outputs = counts.written_arrays(prog)
        out.append(p)
    return out


def prng_key(seed: int, *salts: str):
    """A key from a seed of any size (PRNGKey keeps 32 bits only)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for s in salts:
        key = jax.random.fold_in(key, zlib.crc32(s.encode()))
    return key


def input_ranges(config: dict, program: str, arrays) -> dict[str, tuple[float, float]]:
    """Uniform range of each input array: per program and array, with
    ``"*"`` as the default at either level."""
    spec = config["inputs"]
    per = spec.get(program, spec.get("*", {}))
    out = {}
    for a in arrays:
        lo, hi = per.get(a.name, per.get("*"))
        out[a.name] = (float(lo), float(hi))
    return out


def make_inputs(cell: Cell, p: Prog, seed: int, dtype=None) -> dict:
    """The program's inputs, drawn on the device from ``seed`` in one
    jitted call (the same seed gives the same arrays)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    key = ("inputs", p.name, jnp.dtype(dtype).name)
    if key not in cell.jits:
        arrays = p.program.input_arrays
        ranges = input_ranges(cell.config, p.name, arrays)

        def gen(k):
            return {a.name: jax.random.uniform(
                jax.random.fold_in(k, zlib.crc32(a.name.encode())), a.shape,
                jnp.float32, *ranges[a.name]).astype(dtype) for a in arrays}

        cell.jits[key] = jax.jit(gen)
    return cell.jits[key](prng_key(seed, "inputs", p.name))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclass
class Tally:
    """Calls and wall seconds of one program over the window."""

    calls: int = 0
    seconds: float = 0.0

    @property
    def per_call_s(self) -> float:
        return self.seconds / self.calls


def slice_calls(slice_s: float, est_s: float) -> int:
    """Calls that fill a slice of ``slice_s`` at ``est_s`` a call (at least one)."""
    return max(1, round(slice_s / est_s))


def window_rounds(seconds: float, traffic: dict) -> tuple[int, float]:
    """The window's number of rounds and the length of each: as close to
    the traffic's ``round_s`` as divides ``seconds`` evenly."""
    n = max(1, round(seconds / float(traffic["round_s"])))
    return n, seconds / n


def run_round(calls: list[Callable[[], Any]], names: list[str], traffic: dict,
              round_s: float, est: list[float], tallies: list[Tally],
              clock=time.perf_counter, block=None, span=None, start: int = 0,
              keep: dict | None = None) -> None:
    """One round of the window; adds each slice to its program's tally.

    ``keep`` maps a program index to a one-element list that receives the
    last output of its slice in this round (the answer checked later).
    ``start`` rotates which program opens the round.
    """
    import contextlib

    block = block or (lambda x: x)
    span = span or (lambda name: contextlib.nullcontext())
    n = len(calls)
    order = [(start + k) % n for k in range(n)]
    mode = traffic["mode"]

    def one_slice(i: int, k: int) -> None:
        t0 = clock()
        with span(f"slice:{names[i]}"):
            with span(f"dispatch:{names[i]}"):
                out = None
                for _ in range(k):
                    out = calls[i]()
            with span("block"):
                block(out)
        tallies[i].calls += k
        tallies[i].seconds += clock() - t0
        if keep is not None and i in keep:
            keep[i][0] = out

    if mode == "equal_slices":
        slice_s = round_s / n
        for i in order:
            one_slice(i, slice_calls(slice_s, est[i]))
    elif mode == "steps":
        t_end = clock() + round_s
        while True:
            for i in order:
                one_slice(i, 1)
            if clock() >= t_end:
                break
    else:
        raise ValueError(f"unknown traffic mode {mode!r}")


class CompileCounter:
    """Counts traces, compilations and persistent-cache loads while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def widest_gap(out: np.ndarray, ref: np.ndarray) -> float:
    """max |out - ref| over max |ref|: the widest gap, as a share of the
    reference's range (NaN or inf in ``out`` reads as inf)."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        return math.inf
    if not np.isfinite(out).all():
        return math.inf
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def compare(outputs: dict, ref: dict, arrays: list[str]) -> float:
    """The widest gap over the arrays a program writes."""
    missing = [a for a in arrays if a not in ref]
    if missing:
        raise KeyError(f"the reference does not compute {missing}")
    return max(widest_gap(outputs[a], ref[a]) for a in arrays)


def reference_outputs(cell: Cell, p: Prog, seed: int) -> dict:
    """The plain reference on the program's inputs, in float32 with its
    matrix products at ``highest`` precision, as host arrays."""
    import jax

    key = ("reference", p.name)
    if key not in cell.jits:
        cell.jits[key] = jax.jit(lambda x: cell.reference(p.name, x, p.sizes))
    with jax.default_matmul_precision("highest"):
        out = cell.jits[key](make_inputs(cell, p, seed))
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache in ``<checkout>/.jax_cache``, a
    fixed path (it is part of the cache's key), for every program however
    fast it compiles.  Entry points call this; the harness never does."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: with a size limit set in the environment, JAX scans every
    # entry's access-time file before each write, and one entry without it
    # (as a cache written with no limit leaves them) makes every write fail.
    jax.config.update("jax_compilation_cache_max_size", -1)


def use_precision(config: dict) -> None:
    """Run matrix products at the precision the configuration states
    (``matmul_precision``, a value of JAX's ``jax_default_matmul_precision``),
    which the compiler's einsums follow."""
    import jax

    if "matmul_precision" in config:
        jax.config.update("jax_default_matmul_precision", config["matmul_precision"])


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_stats() -> dict:
    """The first device's memory counters, as the backend reports them."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats and "peak_bytes_in_use" in stats else None


def peaks_for(kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """Peak FLOP/s and bytes/s of a device kind; an unknown kind is an error."""
    table = load_json(path)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name} ({sorted(table)})")
    return table[kind]


def log(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def compile_programs(progs: list[Prog]) -> tuple[list[Callable], list[Any], Any]:
    """``Daisy.compile`` of every program, as a user gets it: the shipped
    transfer-tuning database and the platform's backend."""
    from repro.core import Daisy, TuningDatabase
    from repro.core.database import default_pretuned_path

    daisy = Daisy(db=TuningDatabase.load(default_pretuned_path("xla")))
    fns, plans = [], []
    for p in progs:
        fn, plan = daisy.compile(p.program)
        fns.append(fn)
        plans.append(plan)
    return fns, plans, daisy


def estimate(call: Callable[[], Any], block: Callable, budget_s: float = 0.2,
             clock=time.perf_counter) -> float:
    """Seconds a call takes when calls run back to back (after warm-up)."""
    t0 = clock()
    block(call())
    first = clock() - t0
    n = max(2, min(1000, int(budget_s / max(first, 1e-6))))
    t0 = clock()
    out = None
    for _ in range(n):
        out = call()
    block(out)
    return (clock() - t0) / n


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             compile_fn: Callable = compile_programs) -> dict:
    """One run of ``cell``; returns the result object (the last line).  A
    configuration with a ``builder`` is run by ``bench/<builder>.py``'s ``run_cell``."""
    if "builder" in cell.config:
        runner = importlib.import_module(f"bench.{cell.config['builder']}")
        return runner.run_cell(cell, seed, seconds, trace, t_start)
    dev = device_info()
    counter = CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, t_start, compile_fn, dev, counter)
    finally:
        counter.close()


def _run(cell, seed, seconds, trace, t_start, compile_fn, dev, counter) -> dict:
    import jax

    block = jax.block_until_ready
    use_precision(cell.config)
    progs = build_programs(cell.config, cell.traffic)
    names = [p.name for p in progs]
    fns, plans, daisy = compile_fn(progs)
    inputs = [make_inputs(cell, p, seed) for p in progs]
    calls = [(lambda f=f, x=x: f(x)) for f, x in zip(fns, inputs)]
    est = [estimate(c, block) for c in calls]  # compiles (or loads) each program
    rounds, round_s = window_rounds(seconds, cell.traffic)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    checked_round = [int(r) for r in rng.integers(0, rounds, size=len(progs))]
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; per-call estimates (ms): "
        + json.dumps({n: round(e * 1e3, 4) for n, e in zip(names, est)}))
    if daisy is not None:
        from repro.kernels import nest_kernel

        log(f"compiler: {daisy.cache_stats}; Pallas nest kernels emitted: "
            f"{dict(nest_kernel.EMITTED)}")

    tallies = [Tally() for _ in progs]
    per_round = []  # [round][program] = [calls, seconds], to place a slow stretch
    kept = {i: [None] for i in range(len(progs))}
    gc.collect()  # set-up's garbage is not the window's to collect
    counter.armed = True
    w0 = time.perf_counter()
    for r in range(rounds):
        keep = {i: kept[i] for i in range(len(progs)) if checked_round[i] == r}
        this = [Tally() for _ in progs]
        run_round(calls, names, cell.traffic, round_s, est, this,
                  block=block, start=r, keep=keep)
        for t, u in zip(tallies, this):
            t.calls += u.calls
            t.seconds += u.seconds
        per_round.append([[u.calls, u.seconds] for u in this])
        est = [t.per_call_s for t in tallies]  # later rounds fill their slices better
    window_s = time.perf_counter() - w0
    counter.armed = False
    in_window_compiles = counter.count
    log(f"window {window_s:.3f} s in {rounds} rounds, compilations inside it: "
        f"{in_window_compiles}")

    trace_summary = None
    if trace:
        trace_summary = traced_round(calls, names, cell.traffic, round_s, est, block)
    mem = peak_bytes()

    # the per-program table
    rows = []
    for p, t, plan in zip(progs, tallies, plans):
        rows.append({"program": p.name, "calls": t.calls, "seconds": t.seconds,
                     "ms_per_call": t.per_call_s * 1e3, "flops": p.flops, "bytes": p.nbytes,
                     "sources": [n.source for n in plan.nests],
                     "recipes": [n.recipe.kind for n in plan.nests]})
        log(json.dumps(rows[-1]))

    # the answers checked: one slice's last output per program, drawn from the seed
    answers = [{k: np.asarray(v) for k, v in kept[i][0].items() if k in p.outputs}
               for i, p in enumerate(progs)]
    del kept, calls, fns, inputs
    pass_s = None
    if trace:
        pass_s = [sum(r.seconds for r in daisy.explain(p.program).records) for p in progs]
    del daisy
    checks = {}
    limits = cell.config["limits"]
    for p, ans in zip(progs, answers):
        gap = compare(ans, reference_outputs(cell, p, seed), p.outputs)
        checks[p.name] = {"value": gap, "limit": float(limits[p.name])}
    failed = sum(not (c["value"] <= c["limit"]) for c in checks.values())

    peak = peaks_for(dev["kind"])
    record = {
        "programs": [dict(r, pass_s=(pass_s[i] if pass_s else None))
                     for i, r in enumerate(rows)],
        "peak": peak, "window_s": window_s, "setup_s": setup_s,
        "trace": trace_summary, "in_window_compiles": in_window_compiles,
    }
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.readers[m["name"]](record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=mem)
    result = {"correct": failed == 0, "attempted": len(checks), "failed": failed,
              "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {"device_ops": trace_summary["device_ops"],
                               "idle_gaps": trace_summary["idle_gaps"]}
    result["checks"] = checks
    write_table(cell.name, seed, trace, {"rows": rows, "rounds": per_round,
                                         "record": record, "result": result})
    return result


def traced_round(calls, names, traffic, round_s, est, block) -> dict:
    """One more round of the window's length, under the profiler, reduced to
    busy time, top device ops and idle gaps by host span.  The trace is
    deleted once read."""
    import shutil

    import jax

    from . import trace_reduce

    tdir = OUT_DIR / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    tallies = [Tally() for _ in calls]
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            run_round(calls, names, traffic, round_s, est, tallies, block=block,
                      span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    summary = trace_reduce.reduce_trace(
        trace_reduce.read_xplane(trace_reduce.find_xplane(str(tdir))))
    shutil.rmtree(tdir, ignore_errors=True)
    log(f"traced round: busy {summary['busy_s']:.6f} s of {summary['window_s']:.6f} s")
    return summary


def write_table(cell: str, seed: int, trace: bool, obj: dict) -> None:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{cell}.{seed}.trace{int(trace)}.json"
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
