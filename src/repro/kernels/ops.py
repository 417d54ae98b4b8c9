"""Jit'd public wrappers around the Pallas kernels.

Backend selection (``repro.kernels.ops.BACKEND`` or per-call ``backend=``):
  * ``'xla'``               — pure-jnp reference path (default for dry-run/
                              training on this CPU container; XLA fuses it)
  * ``'pallas_interpret'``  — Pallas kernels executed in interpret mode
                              (CPU correctness validation)
  * ``'pallas'``            — Pallas compiled for TPU (the deploy target)

``einsum2`` is the hook the daisy codegen uses to route the BLAS-3 idiom of
a canonical nest into the Pallas GEMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash
from .gemm import gemm as _gemm
from .rmsnorm import rmsnorm as _rmsnorm

BACKEND = "xla"


def _use_pallas(backend):
    b = backend or BACKEND
    return b in ("pallas", "pallas_interpret"), b == "pallas_interpret"


def matmul(x, y, *, tile=None, backend=None):
    pallas, interp = _use_pallas(backend)
    if not pallas:
        return ref.matmul(x, y)
    bm, bn, bk = tile or (128, 128, 128)
    return _gemm(x, y, block_m=bm, block_n=bn, block_k=bk, interpret=interp)


# Above this many score elements (Sq*Skv) the XLA path switches to the
# chunked online-softmax formulation (bounded HBM working set).
CHUNKED_ATTN_THRESHOLD = 1 << 22


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              tile=None, backend=None):
    pallas, interp = _use_pallas(backend)
    if not pallas:
        if q.shape[1] * k.shape[1] > CHUNKED_ATTN_THRESHOLD and q.shape[1] > 1:
            return ref.attention_chunked(
                q, k, v, causal=causal, window=window, q_offset=q_offset)
        return ref.attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    bq, bk_ = tile or (128, 128)
    return _flash(q, k, v, causal=causal, window=window, q_offset=q_offset,
                  block_q=bq, block_k=bk_, interpret=interp)


def rmsnorm(x, gamma, *, eps=1e-6, backend=None):
    pallas, interp = _use_pallas(backend)
    if not pallas:
        return ref.rmsnorm(x, gamma, eps=eps)
    shape = x.shape
    out = _rmsnorm(x.reshape(-1, shape[-1]), gamma, eps=eps, interpret=interp)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# daisy codegen hook: 2-operand einsum -> Pallas GEMM
# ---------------------------------------------------------------------------
class NotAContraction(ValueError):
    """The einsum is not a clean 2-operand contraction ``einsum2`` lowers."""


def einsum2(sub_a: str, sub_b: str, sub_out: str, a, b, *, tile=None,
            interpret: bool = True):
    """Lower a clean 2-operand contraction to the tiled GEMM kernel.

    Only handles the no-batch-dim case (every letter is either contracted or
    appears in the output exactly once); anything else raises
    ``NotAContraction`` so the caller falls back to jnp.einsum.
    """
    letters = set(sub_a) | set(sub_b)
    contracted = [l for l in letters if l in sub_a and l in sub_b and l not in sub_out]
    kept_a = [l for l in sub_a if l in sub_out]
    kept_b = [l for l in sub_b if l in sub_out and l not in kept_a]
    if (
        len(set(sub_a)) != len(sub_a)
        or len(set(sub_b)) != len(sub_b)
        or sorted(sub_out) != sorted(kept_a + kept_b)
        or not contracted
    ):
        raise NotAContraction(f"{sub_a},{sub_b}->{sub_out}")

    # move contracted letters last in a, first in b; flatten to 2-D
    perm_a = [sub_a.index(l) for l in kept_a] + [sub_a.index(l) for l in contracted]
    perm_b = [sub_b.index(l) for l in contracted] + [sub_b.index(l) for l in kept_b]
    a2 = jnp.transpose(a, perm_a)
    b2 = jnp.transpose(b, perm_b)
    ka = 1
    for l in kept_a:
        ka *= a.shape[sub_a.index(l)]
    kc = 1
    for l in contracted:
        kc *= a.shape[sub_a.index(l)]
    kb = 1
    for l in kept_b:
        kb *= b.shape[sub_b.index(l)]
    a2 = a2.reshape(ka, kc)
    b2 = b2.reshape(kc, kb)
    bm, bn, bk = tile or (128, 128, 128)
    out = _gemm(a2, b2, block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    # reshape/transpose to the requested output order
    out = out.reshape([a.shape[sub_a.index(l)] for l in kept_a]
                      + [b.shape[sub_b.index(l)] for l in kept_b])
    cur = kept_a + kept_b
    perm_o = [cur.index(l) for l in sub_out]
    return jnp.transpose(out, perm_o)
