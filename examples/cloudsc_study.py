"""The CLOUDSC case study end to end (paper §5).

Run:  PYTHONPATH=src python examples/cloudsc_study.py
"""
import numpy as np
import jax

from repro.cloudsc import erosion_program, mini_cloudsc_program
from repro.cloudsc.erosion import physical_inputs
from repro.cloudsc.scheme import scheme_inputs
from repro.core import Schedule, compile_jax, normalize
from repro.core.util import time_fn
from repro.device import use_compile_cache


def main() -> None:
    use_compile_cache()
    nproma, klev = 128, 137
    p = erosion_program(nproma, klev)
    pn = normalize(p)
    print(f"erosion: scalar temps expanded to "
          f"{[a.shape for a in pn.arrays if a.name in pn.temps]}")
    inp = {k: np.asarray(v, np.float32) for k, v in physical_inputs(nproma, klev).items()}
    f0 = jax.jit(compile_jax(p, Schedule(mode="as_written", use_idioms=False)))
    f1 = jax.jit(compile_jax(pn, Schedule(mode="canonical", use_idioms=False)))
    err = np.abs(np.asarray(f0(inp)["ZTP1"]) - np.asarray(f1(inp)["ZTP1"])).max()
    t0, t1 = time_fn(lambda: f0(inp), repeats=3), time_fn(lambda: f1(inp), repeats=5)
    print(f"erosion nest: original {t0/1e3:.1f} ms -> normalized {t1/1e3:.2f} ms "
          f"({t0/t1:.0f}x, maxerr {err:.1e}; paper Table 1: 6.2x)")

    ps = mini_cloudsc_program(nproma, klev)
    psn = normalize(ps)
    inps = {k: np.asarray(v, np.float32) for k, v in scheme_inputs(nproma, klev).items()}
    g0 = jax.jit(compile_jax(ps, Schedule(mode="as_written", use_idioms=False)))
    g1 = jax.jit(compile_jax(psn, Schedule(mode="canonical", use_idioms=False)))
    t0, t1 = time_fn(lambda: g0(inps), repeats=3), time_fn(lambda: g1(inps), repeats=5)
    print(f"mini scheme:  as-written {t0/1e3:.1f} ms -> daisy {t1/1e3:.2f} ms "
          f"({t0/t1:.1f}x; the JK-carried flux recurrence stays sequential)")
    print("OK")


if __name__ == "__main__":
    main()
