"""Quickstart: normalize a loop nest and schedule it with daisy.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (
    Array, Computation, Loop, Program, acc, Daisy, execute_numpy, fingerprint,
    normalize,
)
from repro.core.scheduler import random_inputs
from repro.device import use_compile_cache

use_compile_cache()

# -- 1. author a loop nest (the paper's Fig. 1 "gemm_2": bad loop order) -----
NI, NJ, NK = 256, 256, 256
scale = Computation("scale", acc("C", "i", "j"), (acc("C", "i", "j"),),
                    lambda c: 1.2 * c)
mac = Computation("mac", acc("C", "i2", "j2"),
                  (acc("A", "i2", "k"), acc("B", "k", "j2")),
                  lambda a, b: 1.5 * a * b, accumulate="+")
prog = Program(
    "my_gemm",
    (Array("A", (NI, NK)), Array("B", (NK, NJ)), Array("C", (NI, NJ))),
    (
        Loop("i", NI, body=(Loop("j", NJ, body=(scale,)),)),
        Loop("j2", NJ, body=(Loop("k", NK, body=(Loop("i2", NI, body=(mac,)),)),)),
    ),
)

# -- 2. a priori normalization: maximal fission + stride minimization --------
norm = normalize(prog)
print("canonical nests:")
for nest in norm.body:
    print("  ", fingerprint(nest)[:100])

# -- 3. schedule through daisy (idiom detection + transfer tuning) -----------
daisy = Daisy()
print(daisy.explain(prog).report())       # per-pass wall time + nest deltas
daisy.seed([prog], search=False)          # seed the database from this program
fn, plan = daisy.compile(prog)            # normalize -> DB lookup -> lower
for p in plan.nests:
    print(f"nest idiom={p.idiom:12s} recipe={p.recipe.kind:10s} source={p.source}")

# -- 4. run it and check against the interpreter oracle ----------------------
inp = random_inputs(prog, seed=0)
out = fn(inp)
ref = execute_numpy(prog, {k: v.astype(np.float64) for k, v in inp.items()})
err = np.abs(np.asarray(out["C"], np.float64) - ref["C"]).max()
print(f"max |err| vs oracle: {err:.2e}")
assert err < 1e-2
print("OK")
