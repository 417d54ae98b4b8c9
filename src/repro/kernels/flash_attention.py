"""Pallas TPU flash attention (fwd) — causal / sliding-window / GQA.

Blockwise online-softmax attention (FlashAttention-style, adapted to the TPU
memory hierarchy): grid ``(batch*q_heads, Sq/bq, Skv/bk)`` with the KV block
dimension innermost ('arbitrary'); running max/denominator/accumulator live
in VMEM scratch.  GQA is handled *inside the index map* — the K/V BlockSpecs
divide the head index by the group size, so KV blocks are fetched once per
group without materializing repeated heads in HBM.

Fully-masked KV blocks are skipped with ``pl.when`` (the TPU analogue of the
paper's guard-aware scheduling: the canonical form knows the mask structure
a priori, so the schedule can prune the iteration space).

Backward uses the XLA reference (jax.custom_vjp); the dry-run/training path
is pure XLA and differentiates natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int | None, q_offset: int,
    block_q: int, block_k: int, n_kv: int, kv_len: int,
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def _process():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len  # padded keys are never attended
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_ref[...], l_ref[...] = m_new, l_new

    # prune KV blocks that are fully masked for this q tile (a-priori
    # schedule pruning: the canonical form exposes the mask structure)
    live = j * block_k < kv_len
    if causal:
        live &= (j * block_k) <= (q_offset + (i + 1) * block_q - 1)
    if window is not None:
        live &= ((j + 1) * block_k - 1) > (q_offset + i * block_q) - window
    pl.when(live)(_process)

    @pl.when(j == n_kv - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def _flash_fwd(
    q, k, v, *, causal, window, q_offset, block_q, block_k, interpret
):
    """q: (BHq, Sq, D); k, v: (BHkv, Skv, D) -> (BHq, Sq, D)."""
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    assert bhq % bhkv == 0
    group = bhq // bhkv
    scale = 1.0 / (d ** 0.5)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad_q, pad_k = (-sq) % bq, (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # pad keys so padded positions are masked out by q_pos >= k_pos only
        # for causal; for safety always mask via an explicit validity test
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sq, Skv = q.shape[1], k.shape[1]
    n_kv = Skv // bk

    kern = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        block_q=bq, block_k=bk, n_kv=n_kv, kv_len=skv,
    )
    out = pl.pallas_call(
        kern,
        grid=(bhq, Sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, grp=group: (b // grp, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, grp=group: (b // grp, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]


def flash_attention(
    q, k, v, *, causal=True, window=None, q_offset=0,
    block_q=128, block_k=128, interpret=True,
):
    """Flash attention over (BH, S, D) tensors (GQA via BHq = g * BHkv)."""
    return _flash_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
