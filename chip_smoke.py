"""Smoke run of the compiler and serving paths on a TPU.

    python chip_smoke.py             # one chip: compiler path, then serving
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One process drives every phase.  A phase that fails raises, so the script
exits non-zero; it also exits non-zero, before any phase runs, when JAX's
default backend is not a TPU.  Lines starting ``smoke:`` are progress
output (compile seconds, peak device bytes, wall time per phase), not
metrics.  The last line of a passing run is one JSON object naming the
device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (functions below take their configuration and sizes as arguments,
so ``tests/test_chip_smoke.py`` runs them on the CPU at reduced size):

* compiler path — ``Daisy`` with the platform's backend (compiled Pallas on
  a TPU) on PolyBench and CLOUDSC programs, with every canonical nest the
  Pallas kernels cover routed to its kernel.  At ``mini`` size each output
  is checked against the float64 ``execute_numpy`` oracle; at the real size
  against ``Daisy(backend="xla")`` on the same device.
* serving path — ``ServingEngine`` on h2o-danube-3-4b at its published
  widths (random weights from ``--seed``): eight greedy requests must all
  complete with no failure and no degradation, and the first-token logits
  of the decode path must agree with ``model.forward``.
* ``--chips 4`` — CLOUDSC column-sharded over four devices against the same
  program on one, and the serving engine on a (data=1, model=4) mesh
  against the one-device engine.

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``.jax_cache/`` in the checkout; the script writes nothing else.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cloudsc import (  # noqa: E402
    column_mesh,
    compile_scheme,
    mini_cloudsc_program,
    saturation_chain_inputs,
    saturation_chain_program,
)
from repro.cloudsc.scheme import scheme_inputs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import Daisy, Recipe, TilingError, TuningDatabase, execute_numpy  # noqa: E402
from repro.core import plan_nest_tiling  # noqa: E402
from repro.core.database import default_pretuned_path  # noqa: E402
from repro.core.embedding import embed_nest  # noqa: E402
from repro.core.scheduler import random_inputs  # noqa: E402
from repro.device import use_compile_cache  # noqa: E402
from repro.kernels import nest_kernel  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.sharding import param_specs  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.polybench import BENCHMARKS  # noqa: E402
from repro.serve import RequestState, ServeConfig, ServingEngine  # noqa: E402

POLYBENCH = ("gemm", "2mm", "atax", "correlation", "jacobi-2d")
VARIANTS = ("a", "b")
MODEL = "h2o-danube-3-4b"

# Output error is measured as max|out - ref| / max|ref| per checked array.
# TPU f32 matmuls default to one bf16 pass (8 mantissa bits, 2^-8 ~ 4e-3
# relative per product); over a K-term dot product of positive terms the
# rounding averages out to well under that, so 1e-2 bounds it with headroom
# while a wrong loop order, a dropped tile or a bad halo misses by O(1).
POLYBENCH_TOL = 1e-2
# CLOUDSC has no matmul: only the transcendentals (exp/log of the IFS
# thermodynamic helpers) differ from float64 numpy, by a few f32 ulps, and
# the 137-level flux recurrence damps them (fall weight < 1).
CLOUDSC_TOL = 1e-3
# bf16 activations and weights: the cached decode path and the full-sequence
# forward round in different orders through 24 layers, ~1e-2 of the logit
# range apart; a wrong cache slot or mask moves logits by their whole range.
LOGIT_TOL = 5e-2
# The sharded CLOUDSC program runs the same per-column arithmetic as the
# one-device program; only XLA's fusion choices may differ.
SHARD_TOL = 1e-5


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def rel_err(out, ref) -> float:
    ref = np.asarray(ref, np.float64)
    err = np.abs(np.asarray(out, np.float64) - ref).max()
    return float(err / max(np.abs(ref).max(), 1e-30))


# ---------------------------------------------------------------------------
# compiler path
# ---------------------------------------------------------------------------
def compiler_programs(size: str, nproma: int, klev: int, seed: int) -> list[tuple]:
    """``(program, inputs, checked arrays, tolerance)`` for every compiler
    case: the PolyBench A/B variants at ``size`` and the two CLOUDSC
    programs at ``nproma`` x ``klev``, with inputs drawn from ``seed``."""
    cases = []
    for name in POLYBENCH:
        bench = BENCHMARKS[name]
        for v in VARIANTS:
            prog = bench.make(v, size)
            cases.append((prog, random_inputs(prog, seed=seed, dtype=np.float64),
                          (bench.output,), POLYBENCH_TOL))
    cases.append((mini_cloudsc_program(nproma, klev), scheme_inputs(nproma, klev, seed),
                  ("ZTP1", "ZQSMIX", "ZQL", "ZQI", "TENDQ"), CLOUDSC_TOL))
    cases.append((saturation_chain_program(nproma, klev),
                  saturation_chain_inputs(nproma, klev, seed=seed), ("TEND",), CLOUDSC_TOL))
    return cases


def pallas_kind(program, nest, idiom: str) -> str | None:
    """The Pallas recipe kind that covers ``nest``, or None."""
    if idiom == "blas3":
        return "pallas_gemm"
    try:
        plan = plan_nest_tiling(program, nest)
    except TilingError:
        return None
    return "pallas_nest" if plan.kind == "parallel" else "pallas_reduce"


def pallas_database(programs) -> TuningDatabase:
    """The shipped transfer-tuned database, with every canonical nest of
    ``programs`` that a Pallas kernel covers moved onto that kernel's recipe
    (the shipped recipes were tuned for XLA)."""
    db = TuningDatabase.load(default_pretuned_path("xla"))
    scout = Daisy(db=db, backend="xla")
    for prog in programs:
        plan = scout.plan(prog)
        for nest, nplan in zip(plan.program.body, plan.nests):
            kind = pallas_kind(plan.program, nest, nplan.idiom)
            if kind is None:
                continue
            recipe = Recipe(kind=kind, notes="chip smoke: Pallas route")
            if db.lookup_exact(nplan.fingerprint) is None:
                db.add(nplan.fingerprint, embed_nest(plan.program, nest), recipe,
                       provenance="chip_smoke")
            else:
                db.replace_entry(nplan.fingerprint, recipe, provenance="chip_smoke")
    return db


def run_program(daisy: Daisy, prog, inputs) -> tuple[dict, float, str]:
    """Compile ``prog`` through ``daisy``, run it once on ``inputs``;
    returns (outputs as numpy, compile seconds, compiled HLO text)."""
    fn, _ = daisy.compile(prog)
    args = {k: np.asarray(v, np.float32) for k, v in inputs.items()
            if k in {a.name for a in prog.input_arrays}}
    t0 = time.perf_counter()
    compiled = fn.lower(args).compile()
    compile_s = time.perf_counter() - t0
    out = jax.tree_util.tree_map(np.asarray, compiled(args))
    return out, compile_s, compiled.as_text()


def compiler_phase(size: str, nproma: int, klev: int, oracle: bool,
                   seed: int = 0) -> list[dict]:
    """Compile and run every compiler case under ``Daisy()`` (the platform's
    Pallas backend) with Pallas recipes, and check
    each output against the float64 oracle (``oracle=True``) or against
    ``Daisy(backend="xla")`` on the same device.  Raises on any mismatch,
    when no Pallas kernel was built at all, and when a program with Pallas
    recipes compiles to HLO without a ``tpu_custom_call``.  (The stencils'
    time loop carries a dependence, so jacobi-2d takes the XLA path.)"""
    cases = compiler_programs(size, nproma, klev, seed)
    db = pallas_database([c[0] for c in cases])
    daisy = Daisy(db=db)
    if jax.default_backend() == "tpu" and daisy.interpret:
        raise AssertionError(f"Pallas would run interpreted on a TPU ({daisy.backend})")
    xla = Daisy(db=db, backend="xla")
    rows = []
    for prog, inputs, arrays, tol in cases:
        before = dict(nest_kernel.EMITTED)
        out, compile_s, hlo = run_program(daisy, prog, inputs)
        kernels = sum(nest_kernel.EMITTED.values()) - sum(before.values())
        pallas_nests = sum(n.recipe.kind.startswith("pallas")
                           for n in daisy.plan(prog).nests)
        custom_calls = hlo.count("tpu_custom_call")
        if daisy.backend == "pallas" and pallas_nests and not custom_calls:
            raise AssertionError(f"{prog.name}: compiled HLO holds no Pallas kernel")
        if oracle:
            ref = execute_numpy(prog, inputs)
            ref_name = "float64 oracle"
        else:
            ref, _, _ = run_program(xla, prog, inputs)
            ref_name = "xla"
        errs = {k: rel_err(out[k], ref[k]) for k in arrays}
        for k, e in errs.items():
            if not (np.isfinite(out[k]).all() and e <= tol):
                raise AssertionError(
                    f"{prog.name} [{size}] {k}: rel err {e} vs {ref_name} "
                    f"exceeds {tol}")
        row = {"program": prog.name, "size": size, "backend": daisy.backend,
               "pallas_nests": pallas_nests, "nest_kernels": kernels,
               "tpu_custom_calls": custom_calls, "compile_s": compile_s,
               "max_rel_err": max(errs.values()), "vs": ref_name}
        log(json.dumps(row))
        rows.append(row)
    if not any(r["nest_kernels"] or r["tpu_custom_calls"] for r in rows):
        raise AssertionError("no Pallas kernel was built")
    return rows


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------
def prompts_for(vocab: int, n: int, lo: int, hi: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(0, vocab, size=int(s)).astype(np.int32) for s in lens]


def first_token_logits(cfg, params, prompt: np.ndarray, max_len: int):
    """(decode-path logits, forward logits) at the last prompt position."""
    state = M.init_decode_state(cfg, 1, max_len, ring=False)
    dec, _ = jax.jit(partial(M.decode_step, cfg))(params, state, jnp.asarray(prompt[None]))
    fwd = jax.jit(partial(M.forward, cfg))(params, {"tokens": jnp.asarray(prompt[None])})
    return np.asarray(dec[0, -1], np.float32), np.asarray(fwd[0, -1], np.float32)


def serve(cfg, params, scfg: ServeConfig, prompts, mesh=None) -> ServingEngine:
    """Drain ``prompts`` through a ``ServingEngine``; raises unless every
    request COMPLETED with ``max_new_tokens`` tokens and the engine recorded
    no failure and no degradation."""
    eng = ServingEngine(cfg, params, scfg, mesh=mesh)
    handles = [eng.submit(p) for p in prompts]
    eng.drain()
    bad = [(h.rid, h.state.value, repr(h.error)) for h in handles
           if h.state is not RequestState.COMPLETED
           or len(h.tokens) != scfg.max_new_tokens]
    if bad or eng.failed or eng.degradations:
        raise AssertionError(f"serving: unfinished {bad}, failed "
                             f"{sorted(eng.failed)}, degradations {eng.degradations}")
    return eng


def serving_phase(cfg, scfg: ServeConfig, n_requests: int, prompt_lens: tuple[int, int],
                  seed: int) -> dict:
    """Serve ``n_requests`` seeded prompts on ``cfg`` with random weights,
    then check the first token against ``model.forward``."""
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"{cfg.name}: {n_params} params ({cfg.dtype}) initialised in "
        f"{time.perf_counter() - t0:.1f} s; model kernels: ops.BACKEND={ops.BACKEND!r}")
    prompts = prompts_for(cfg.vocab, n_requests, *prompt_lens, seed=seed)
    t0 = time.perf_counter()
    eng = serve(cfg, params, scfg, prompts)
    served_s = time.perf_counter() - t0
    dec, fwd = first_token_logits(cfg, params, prompts[0], scfg.max_len)
    err = rel_err(dec, fwd)
    first = eng.results[0][0]
    gap = float(fwd.max() - fwd[first]) / float(np.abs(fwd).max())
    if not (np.isfinite(dec).all() and err <= LOGIT_TOL and gap <= LOGIT_TOL):
        raise AssertionError(f"first token: decode vs forward logits rel err {err}, "
                             f"engine token {first} trails the forward max by {gap} "
                             f"(tolerance {LOGIT_TOL})")
    row = {"model": cfg.name, "requests": n_requests,
           "prompt_lens": [int(p.size) for p in prompts],
           "tokens": sum(len(t) for t in eng.results.values()),
           "wall_s": served_s, "first_token_logit_rel_err": err,
           "first_token_gap": gap, "peak_bytes_in_use": peak_bytes()}
    log(json.dumps(row))
    return row


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def sharded_cloudsc_phase(nproma: int, klev: int, n_devices: int, seed: int) -> dict:
    """CLOUDSC column-sharded over ``n_devices`` against one device."""
    inputs = {k: np.asarray(v, np.float32)
              for k, v in scheme_inputs(nproma, klev, seed).items()}
    one, _ = compile_scheme(nproma, klev)
    sharded, partition = compile_scheme(nproma, klev, mesh=column_mesh(n_devices))
    if not partition.sharded:
        raise AssertionError(f"CLOUDSC did not shard: {partition}")
    ref, out = one(inputs), sharded(inputs)
    errs = {k: rel_err(out[k], ref[k]) for k in ("ZTP1", "ZQSMIX", "ZQL", "ZQI", "TENDQ")}
    if max(errs.values()) > SHARD_TOL:
        raise AssertionError(f"sharded CLOUDSC vs one device: {errs} > {SHARD_TOL}")
    row = {"program": "mini_cloudsc", "nproma": nproma, "klev": klev,
           "devices": n_devices, "max_rel_err": max(errs.values())}
    log(json.dumps(row))
    return row


def sharded_serving_phase(cfg, scfg: ServeConfig, n_requests: int,
                          prompt_lens: tuple[int, int], seed: int,
                          n_devices: int) -> dict:
    """The serving engine on a (data=1, model=n_devices) mesh against the
    one-device engine: every request completes, and the first-token logits
    of the sharded parameters agree with the one-device ones."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = prompts_for(cfg.vocab, n_requests, *prompt_lens, seed=seed)
    ref, _ = first_token_logits(cfg, params, prompts[0], scfg.max_len)
    serve(cfg, params, scfg, prompts)
    mesh = make_mesh((1, n_devices), ("data", "model"))
    # place the weights before the engine does, so the one-device copy is
    # freed before the sharded steps need device 0's memory
    params = jax.device_put(params, param_specs(
        jax.eval_shape(lambda p: p, params), mesh, cfg=cfg))
    eng = serve(cfg, params, scfg, prompts, mesh=mesh)
    with jax.set_mesh(mesh):
        got, _ = first_token_logits(cfg, eng.params, prompts[0], scfg.max_len)
    err = rel_err(got, ref)
    if not (np.isfinite(got).all() and err <= LOGIT_TOL):
        raise AssertionError(f"sharded first-token logits: rel err {err} > {LOGIT_TOL}")
    row = {"model": cfg.name, "mesh": dict(mesh.shape), "requests": n_requests,
           "first_token_logit_rel_err_vs_one_device": err,
           "peak_bytes_in_use": peak_bytes()}
    log(json.dumps(row))
    return row


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------
def one_chip(seed: int) -> None:
    t0 = time.perf_counter()
    compiler_phase("mini", nproma=16, klev=137, oracle=True, seed=seed)
    compiler_phase("bench", nproma=16384, klev=137, oracle=False, seed=seed)
    log(f"compiler path: {time.perf_counter() - t0:.1f} s wall")
    t0 = time.perf_counter()
    serving_phase(get_config(MODEL),
                  ServeConfig(batch_slots=4, max_len=2048, max_new_tokens=32),
                  n_requests=8, prompt_lens=(16, 1024), seed=seed)
    log(f"serving path: {time.perf_counter() - t0:.1f} s wall")


def four_chips(seed: int) -> None:
    t0 = time.perf_counter()
    sharded_cloudsc_phase(nproma=16384, klev=137, n_devices=4, seed=seed)
    sharded_serving_phase(get_config(MODEL),
                          ServeConfig(batch_slots=4, max_len=2048, max_new_tokens=32),
                          n_requests=4, prompt_lens=(16, 1024), seed=seed, n_devices=4)
    log(f"four-chip paths: {time.perf_counter() - t0:.1f} s wall")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, on four devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX sees "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 1
    use_compile_cache()
    log(f"device {json.dumps(dev)}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"total {time.perf_counter() - t0:.1f} s wall, "
        f"peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
