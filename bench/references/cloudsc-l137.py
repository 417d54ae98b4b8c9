"""Plain reference of the two CLOUDSC programs, in ``jax.numpy``.

Written from the IFS cloud scheme's formulas (the FOEEWM / FOEDEM /
FOELDCPM statement functions, the erosion update of paper Fig. 10a) and
the mini scheme's four stages, independent of the compiler.  Every column
is independent; levels run top to bottom.  Each function takes one
program's inputs and returns every array it writes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

RTT = 273.16
R2ES = 611.21 * 0.621981
R3LES, R3IES = 17.502, 22.587
R4LES, R4IES = 32.19, -0.7
RTWAT = RTT
RTICE = RTT - 23.0
RTWAT_RTICE_R = 1.0 / (RTWAT - RTICE)
RETV = 0.608
RCPD = 1004.709
RLVTT, RLSTT = 2.5008e6, 2.8345e6
RALVDCP, RALSDCP = RLVTT / RCPD, RLSTT / RCPD
R5ALVCP = R3LES * (RTT - R4LES) * RALVDCP
R5ALSCP = R3IES * (RTT - R4IES) * RALSDCP

RG_DT = 0.75
RAUTO = 1.0e-3
RFALL = 0.8


def alpha(t):
    return jnp.minimum(1.0, ((jnp.maximum(RTICE, jnp.minimum(RTWAT, t)) - RTICE)
                             * RTWAT_RTICE_R) ** 2)


def foeewm(t):
    a = alpha(t)
    return R2ES * (a * jnp.exp(R3LES * (t - RTT) / (t - R4LES))
                   + (1.0 - a) * jnp.exp(R3IES * (t - RTT) / (t - R4IES)))


def foedem(t):
    a = alpha(t)
    return (a * R5ALVCP * (1.0 / (t - R4LES) ** 2)
            + (1.0 - a) * R5ALSCP * (1.0 / (t - R4IES) ** 2))


def foeldcpm(t):
    a = alpha(t)
    return a * RALVDCP + (1.0 - a) * RALSDCP


def saturation_adjust(t, q, zqp):
    """One saturation pass: the condensate and the updated (T, q)."""
    qsat = jnp.minimum(0.5, foeewm(t) * zqp)
    cor = 1.0 / (1.0 - RETV * qsat)
    qsat = qsat * cor
    cond = (q - qsat) / (1.0 + qsat * cor * foedem(t))
    return t + foeldcpm(t) * cond, q - cond


def erosion(x):
    zqp = 1.0 / x["PAP"]
    t, q = saturation_adjust(x["ZTP1"], x["ZQSMIX"], zqp)
    t, q = saturation_adjust(t, q, zqp)
    return {"ZTP1": t, "ZQSMIX": q}


def falling(source, rfall):
    """flux[0] = source[0]; flux[k] = rfall * flux[k-1] + source[k]."""
    def step(prev, s):
        f = rfall * prev + s
        return f, f

    _, rest = jax.lax.scan(step, source[0], source[1:])
    return jnp.concatenate([source[:1], rest])


def mini_cloudsc(x):
    t, q = saturation_adjust(x["ZTP1"], x["ZQSMIX"], 1.0 / x["PAP"])
    foel = foeldcpm(t)
    share = foel / (foel + 1.0)
    ql = x["ZQL"] + RAUTO * q * share
    qi = x["ZQI"] + RAUTO * q * (1.0 - share)
    flux = falling(RAUTO * ql, RFALL)
    return {"ZTP1": t, "ZQSMIX": q, "ZQL": ql, "ZQI": qi, "PFPLSL": flux,
            "TENDQ": RG_DT * (q - flux)}


PROGRAMS = {"cloudsc_erosion": erosion, "mini_cloudsc": mini_cloudsc}


def reference(program: str, inputs: dict, sizes: dict) -> dict:
    """Every array ``program`` writes, computed from ``inputs``."""
    return PROGRAMS[program](inputs)
