"""Pallas TPU GEMM — the "library call" target of the BLAS-3 idiom.

Classic MXU-tiled matmul: grid ``(M/bm, N/bn, K/bk)`` with the K dimension
innermost ('arbitrary' semantics) accumulating into a VMEM fp32 scratch
block; M/N blocks are 'parallel'.  Block sizes come from the daisy recipe
database (stride minimization already made the operands row-major-contiguous
along the lane axis, so blocks are (sublane, lane)-aligned by construction).

Target: TPU v5e (MXU 128x128, VMEM ~16MB/core).  Validated on CPU with
``interpret=True`` against ``ref.matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output block; accumulates over the K grid dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm(
    x: jax.Array,
    y: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """``x @ y`` with explicit VMEM tiling. Shapes padded to block multiples."""
    assert x.ndim == 2 and y.ndim == 2 and x.shape[1] == y.shape[0]
    m, k = x.shape
    _, n = y.shape
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))

    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        y = jnp.pad(y, ((0, pad_k), (0, pad_n)))
    M, K = x.shape
    N = y.shape[1]
    n_k = K // bk

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, y)
    return out[:m, :n]
