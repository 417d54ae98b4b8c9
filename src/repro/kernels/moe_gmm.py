"""Pallas TPU grouped matmul — the expert-FFN compute of MoE layers.

Capacity-bucketed formulation: tokens are dispatched to ``x: (E, C, D)``
(E experts, C capacity) and each expert applies its own weight ``w: (E, D, F)``.
Grid ``(E, C/bc, F/bf, D/bd)``; the expert dimension is 'parallel' (it is the
EP-sharded axis on the mesh), D innermost accumulating in VMEM scratch.

This is the TPU adaptation of MegaBlocks-style grouped GEMM: instead of
CSR-indexed block sparsity (a GPU-shared-memory pattern), the canonical form
is a dense per-expert batch — XLA SPMD then shards E across the mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_d: int):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(d == n_d - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 128,
    interpret: bool = True,
) -> jax.Array:
    e, c, d = x.shape
    e2, d2, f = w.shape
    assert e == e2 and d == d2
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    pc, pf, pd = (-c) % bc, (-f) % bf, (-d) % bd
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
    C, D, F = x.shape[1], x.shape[2], w.shape[2]
    n_d = D // bd

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_d=n_d),
        grid=(e, C // bc, F // bf, n_d),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ei, i, j, k: (ei, i, k)),
            pl.BlockSpec((1, bd, bf), lambda ei, i, j, k: (ei, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, i, j, k: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w)
    return out[:, :c, :f]
