"""Plain reference of the 15 PolyBench 4.2 kernels, in ``jax.numpy``.

Written from the kernels' published C code and the semantics of the
suite's builders, independent of the compiler: no normalization, no
recipes, no loop IR.  Each function takes the inputs of one program as a
dict and returns every array the program writes.  The arithmetic runs in
the dtype of the inputs; the benchmark gives it float32 with matrix
products at ``highest`` precision (and, for the control, bfloat16).

Departures of the suite from PolyBench's C code, kept here so that both
compute the same thing: ``correlation`` starts every entry of ``corr`` at
1.0 (the C code zeroes the upper triangle first), and ``gemver``,
``syr2k`` and ``syrk`` keep the C code's order of updates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ALPHA, BETA = 1.5, 1.2


def gemm(x):
    return {"C": BETA * x["C"] + ALPHA * jnp.matmul(x["A"], x["B"])}


def mm2(x):
    tmp = ALPHA * jnp.matmul(x["A"], x["B"])
    return {"tmp": tmp, "D": BETA * x["D"] + jnp.matmul(tmp, x["C2"])}


def mm3(x):
    e = jnp.matmul(x["A"], x["B"])
    f = jnp.matmul(x["C3"], x["D3"])
    return {"E": e, "F": f, "G": jnp.matmul(e, f)}


def _lower(n):
    return jnp.tril(jnp.ones((n, n), bool))


def syrk(x):
    a, c = x["A"], x["C"]
    new = BETA * c + ALPHA * jnp.matmul(a, a.T)
    return {"C": jnp.where(_lower(c.shape[0]), new, c)}


def syr2k(x):
    a, b, c = x["A"], x["B"], x["C"]
    new = BETA * c + ALPHA * jnp.matmul(b, a.T) + ALPHA * jnp.matmul(a, b.T)
    return {"C": jnp.where(_lower(c.shape[0]), new, c)}


def atax(x):
    tmp = jnp.matmul(x["A"], x["x"])
    return {"tmp": tmp, "y": jnp.matmul(x["A"].T, tmp)}


def bicg(x):
    return {"s": jnp.matmul(x["A"].T, x["r"]), "q": jnp.matmul(x["A"], x["p"])}


def gemver(x):
    a = x["A"] + jnp.outer(x["u1"], x["v1"]) + jnp.outer(x["u2"], x["v2"])
    xx = x["x"] + BETA * jnp.matmul(a.T, x["y"]) + x["z"]
    return {"A": a, "x": xx, "w": x["w"] + ALPHA * jnp.matmul(a, xx)}


def gesummv(x):
    tmp = jnp.matmul(x["A"], x["x"])
    return {"tmp": tmp, "y": ALPHA * tmp + BETA * jnp.matmul(x["B"], x["x"])}


def doitgen(x):
    a = x["A"]
    s = jnp.matmul(a.reshape(-1, a.shape[-1]), x["C4"]).reshape(a.shape)
    return {"sum": s, "A": s}


def jacobi_2d(x, steps):
    def sweep(src, dst):
        c = src[1:-1, 1:-1]
        v = 0.2 * (c + src[1:-1, :-2] + src[1:-1, 2:] + src[2:, 1:-1] + src[:-2, 1:-1])
        return dst.at[1:-1, 1:-1].set(v)

    def step(_, ab):
        a, b = ab
        b = sweep(a, b)
        return sweep(b, a), b

    a, b = jax.lax.fori_loop(0, steps, step, (x["A"], x["Bt"]))
    return {"A": a, "Bt": b}


def heat_3d(x, steps):
    def sweep(src, dst):
        c = src[1:-1, 1:-1, 1:-1]
        v = (c + 0.125 * (src[2:, 1:-1, 1:-1] - 2.0 * c + src[:-2, 1:-1, 1:-1])
             + 0.125 * (src[1:-1, 2:, 1:-1] - 2.0 * c + src[1:-1, :-2, 1:-1])
             + 0.125 * (src[1:-1, 1:-1, 2:] - 2.0 * c + src[1:-1, 1:-1, :-2]))
        return dst.at[1:-1, 1:-1, 1:-1].set(v)

    def step(_, ab):
        a, b = ab
        b = sweep(a, b)
        return sweep(b, a), b

    a, b = jax.lax.fori_loop(0, steps, step, (x["A"], x["Bt"]))
    return {"A": a, "Bt": b}


def fdtd_2d(x, steps):
    def step(t, s):
        ex, ey, hz = s
        ey = ey.at[0, :].set(x["fict"][t])
        ey = ey.at[1:, :].set(ey[1:, :] - 0.5 * (hz[1:, :] - hz[:-1, :]))
        ex = ex.at[:, 1:].set(ex[:, 1:] - 0.5 * (hz[:, 1:] - hz[:, :-1]))
        hz = hz.at[:-1, :-1].set(
            hz[:-1, :-1] - 0.7 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] - ey[:-1, :-1]))
        return ex, ey, hz

    ex, ey, hz = jax.lax.fori_loop(0, steps, step, (x["ex"], x["ey"], x["hz"]))
    return {"ex": ex, "ey": ey, "hz": hz}


def correlation(x):
    data = x["data"]
    n = data.shape[0]
    mean = data.sum(axis=0) / n
    std = jnp.sqrt(((data - mean) ** 2).sum(axis=0) / n)
    std = jnp.where(std <= 0.1, jnp.ones_like(std), std)
    data = (data - mean) / (n ** 0.5 * std)
    m = data.shape[1]
    upper = 1.0 + jnp.matmul(data.T, data)
    tri = jnp.triu(jnp.ones((m, m), bool), k=1)
    corr = jnp.where(tri, upper, jnp.where(tri.T, upper.T, jnp.ones_like(upper)))
    return {"mean": mean, "stddev": std, "data": data, "corr": corr}


def covariance(x):
    data = x["data"]
    n = data.shape[0]
    mean = data.sum(axis=0) / n
    data = data - mean
    return {"mean": mean, "data": data, "cov": jnp.matmul(data.T, data) / (n - 1.0)}


KERNELS = {
    "gemm": gemm, "2mm": mm2, "3mm": mm3, "syrk": syrk, "syr2k": syr2k,
    "atax": atax, "bicg": bicg, "gemver": gemver, "gesummv": gesummv,
    "doitgen": doitgen, "correlation": correlation, "covariance": covariance,
}
TIME_STEPPED = {"jacobi-2d": jacobi_2d, "heat-3d": heat_3d, "fdtd-2d": fdtd_2d}


def reference(program: str, inputs: dict, sizes: dict) -> dict:
    """Every array ``program`` writes, computed from ``inputs``.

    ``sizes`` are the program's PolyBench sizes by macro name; the
    time-stepped kernels take their step count from it.
    """
    if program in TIME_STEPPED:
        steps = sizes["TMAX"] if program == "fdtd-2d" else sizes["TSTEPS"]
        return TIME_STEPPED[program](inputs, steps)
    return KERNELS[program](inputs)
