"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(x.dtype)


def attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """q: (BH, Sq, D); k, v: (BHkv, Skv, D).  GQA: the query heads of one
    key-value head read its keys and values as they are (no copy per query
    head), in their own type, with float32 scores and sums.  ``q_offset``,
    the position of the first query, is one for all or one per key-value
    batch row (BHkv,)."""
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    qg = q.reshape(bhkv, bhq // bhkv, sq, d)
    s = jnp.einsum("bgqd,bkd->bgqk", qg, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = s / (d ** 0.5)
    q_pos = jnp.asarray(q_offset)[..., None, None] + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones(q_pos.shape[:-1] + (skv,), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    if mask.ndim == 3:  # one offset per row
        mask = mask[:, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)  # rows with no visible keys -> 0
    o = jnp.einsum("bgqk,bkd->bgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(bhq, sq, d).astype(q.dtype)


def attention_chunked(q, k, v, *, causal=True, window=None, q_offset=0,
                      block_q=512, block_k=1024):
    """Online-softmax attention with bounded HBM working set (the XLA
    analogue of the Pallas flash kernel): double scan over q/kv blocks keeps
    the live scores tensor at (BH, bq, bk) instead of (BH, Sq, Skv).

    Exactly matches ``attention`` (tested); used automatically by ops.attention
    when Sq*Skv is large.
    """
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    group = bhq // bhkv
    if group > 1:
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    scale = 1.0 / (d ** 0.5)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad_q, pad_k = (-sq) % bq, (-skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk
    kb = kp.reshape(bhq, nk, bk, d).transpose(1, 0, 2, 3)  # (nk, BH, bk, d)
    vb = vp.reshape(bhq, nk, bk, d).transpose(1, 0, 2, 3)

    def q_block(qi):
        qc = jax.lax.dynamic_slice_in_dim(qp, qi * bq, bq, axis=1)  # (BH,bq,d)
        q_pos = q_offset + qi * bq + jnp.arange(bq)[:, None]

        def kv_step(carry, inp):
            m, l, acc = carry
            kc, vc, kj = inp
            s = jnp.einsum("bqd,bkd->bqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            k_pos = kj * bk + jnp.arange(bk)[None, :]
            mask = k_pos < skv
            if causal:
                mask &= q_pos >= k_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask[None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.where(mask[None], jnp.exp(s - m_safe[..., None]), 0.0)
            alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqk,bkd->bqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((bhq, bq), -jnp.inf, jnp.float32),
            jnp.zeros((bhq, bq), jnp.float32),
            jnp.zeros((bhq, bq, d), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init,
                                      (kb, vb, jnp.arange(nk)))
        safe = jnp.where(l == 0.0, 1.0, l)
        return (acc / safe[..., None]).astype(q.dtype)  # (BH, bq, d)

    # flash-style remat: the backward recomputes each q block's kv scan
    # instead of saving the (BH, bq, Skv) score residuals — without this,
    # autodiff through the scan retains the full O(Sq*Skv) probabilities.
    q_block = jax.checkpoint(q_block)
    out = jax.lax.map(q_block, jnp.arange(nq))  # (nq, BH, bq, d)
    out = out.transpose(1, 0, 2, 3).reshape(bhq, nq * bq, d)
    return out[:, :sq]


def rmsnorm(x, gamma, *, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * gamma).astype(x.dtype)
