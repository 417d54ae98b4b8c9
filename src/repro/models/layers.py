"""Model building blocks (pure functions over param pytrees).

Every block has a *sequence* form (training/prefill) and a *step* form
(decode with state).  Attention dispatches through ``repro.kernels.ops`` so
the Pallas kernels (validated in interpret mode) and the XLA reference are
interchangeable backends.

Memory-hierarchy notes (TPU adaptation, see DESIGN.md):
  * Mamba / mLSTM scans are CHUNKED — the naive associative scan would
    materialize (B, S, d_inner, d_state), which no HBM holds at the assigned
    shapes; chunking bounds the working set to (B, Q, d_inner, d_state) per
    step, the same a-priori working-set reasoning the paper applies to L1.
  * MoE is dropless: the (token, expert) pairs of the experts held here,
    sorted by expert, go through one grouped product per weight
    (``jax.lax.ragged_dot``), however unevenly they are routed.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..configs.base import ModelConfig, Yarn
from ..kernels import ops

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# sharding hints
# ---------------------------------------------------------------------------
def constrain(x: jax.Array, *entries):
    """with_sharding_constraint that degrades gracefully: axes missing from
    the active mesh or non-dividing dims are dropped; no-op without a mesh.
    Model code can therefore state its preferred layout unconditionally.
    Any other sharding error raises."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    sizes = dict(mesh.shape)
    clean = []
    for d, e in enumerate(entries):
        if e is None or d >= x.ndim:
            clean.append(None)
            continue
        axes = [a for a in ((e,) if isinstance(e, str) else tuple(e)) if a in sizes]
        prod = 1
        for a in axes:
            prod *= int(sizes[a])
        if axes and x.shape[d] % prod == 0 and prod > 1:
            clean.append(axes[0] if len(axes) == 1 else tuple(axes))
        else:
            clean.append(None)
    if all(c is None for c in clean):
        return x
    return jax.lax.with_sharding_constraint(x, jax.sharding.PartitionSpec(*clean))


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def normal(key, shape) -> jax.Array:
    """Standard-normal float32 draws.  The barrier keeps XLA from folding a
    caller's scale into the sampler's own constants, so a jitted init draws
    bit for bit what eager execution draws."""
    return jax.lax.optimization_barrier(jax.random.normal(key, shape))


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (normal(key, shape) * s).astype(dtype)


def yarn_frequencies(dh: int, theta: float, yarn: Yarn) -> tuple[np.ndarray, float]:
    """Inverse frequencies (dh/2,) and cos/sin scale of YaRN-scaled RoPE
    (arXiv:2309.00071; Hugging Face ``_compute_yarn_parameters``): below
    the correction range the frequencies ``theta ** (-2i / dh)`` are kept,
    above it they are divided by ``factor``, and a linear ramp blends the
    two in between."""
    def correction_dim(rotations: float) -> float:
        return (dh * math.log(yarn.original_max_position_embeddings
                              / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    extrapolation = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = extrapolation / yarn.factor * ramp + extrapolation * (1.0 - ramp)
    return inv.astype(np.float32), float(yarn.attention_factor)


def rope(x: jax.Array, positions: jax.Array, theta: float, yarn: Yarn | None = None) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S).  With
    ``yarn``, YaRN's frequencies and its scale on cos and sin."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freqs, scale = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half), 1.0
    else:
        freqs, scale = yarn_frequencies(dh, theta, yarn)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA + optional SWA + optional bias + optional KV cache)
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h * dh), dtype),
        "wk": _dense_init(ks[1], (d, kv * dh), dtype),
        "wv": _dense_init(ks[2], (d, kv * dh), dtype),
        "wo": _dense_init(ks[3], (h * dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((kv * dh,), dtype)
        p["bv"] = jnp.zeros((kv * dh,), dtype)
    return p


def ring_write(ring: jax.Array, new: jax.Array, clen, n_new) -> jax.Array:
    """Write positions ``clen .. clen + n_new - 1`` of ``new`` (..., s, Dh)
    into a ring (..., W, Dh) that holds position p at slot p % W (the
    sequence is the second-to-last axis): each slot takes the latest of
    them that maps to it, or keeps what it held."""
    w, s = ring.shape[-2], new.shape[-2]
    end = clen + n_new
    pos = end - 1 - jnp.mod(end - 1 - jnp.arange(w), w)  # latest position per slot
    src = pos - clen
    rows = jnp.take(new, jnp.clip(src, 0, s - 1), axis=-2)
    return jnp.where((src >= 0)[:, None], rows.astype(ring.dtype), ring)


def project_qkv(x: jax.Array, p: Params, cfg: ModelConfig, memory: jax.Array | None = None):
    """Queries (B, S, H, Dh) and keys and values (B, S_kv, KV, Dh) of x (B,
    S, D), the keys and values of ``memory`` for cross-attention; not yet
    roped."""
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"] + (p["bq"] if "bq" in p else 0.0)
    src = memory if memory is not None else x
    k = src @ p["wk"] + (p["bk"] if "bk" in p else 0.0)
    v = src @ p["wv"] + (p["bv"] if "bv" in p else 0.0)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, -1, kv, dh)
    v = v.reshape(b, -1, kv, dh)
    # layout hints: heads over 'model' where they divide (constrain drops the
    # axis otherwise -> KV replicates over model for GQA kv < mesh)
    q = constrain(q, ("pod", "data"), None, "model", None)
    k = constrain(k, ("pod", "data"), None, "model", None)
    v = constrain(v, ("pod", "data"), None, "model", None)
    return q, k, v


def attention(
    x: jax.Array,  # (B, S, D)
    p: Params,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # (B, S) absolute positions (rope)
    causal: bool = True,
    cache: tuple[jax.Array, jax.Array] | None = None,  # (B, KV, S_cache, Dh)
    clen: jax.Array | int = 0,  # positions the cache holds before this call
    n_new: jax.Array | int | None = None,  # real tokens of the S (the rest padding)
    memory: jax.Array | None = None,  # (B, S_mem, D) for cross-attention
    window: int | None = None,
    yarn: Yarn | None = None,
):
    """Sequence attention (cache=None) or attention over a KV cache, which
    is head-major (B, KV, S_cache, Dh) so that attention reads it as it is.

    A cache no longer than ``window`` is a ring: position p sits at slot
    p % size, keys roped at their absolute positions *before* being
    written, so slot order is irrelevant (softmax is permutation-invariant).
    One token attends over the ring (``q_offset = min(clen, size - 1)``
    masks slots not yet written, and the ring itself bounds the window);
    several tokens (a prefill into an empty ring) attend over themselves
    under the window, and the last ``window`` real ones stay in the ring.
    A longer cache holds every position and applies no window.
    """
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = project_qkv(x, p, cfg, memory)
    if memory is None:
        q = rope(q, positions, cfg.rope_theta, yarn)
        k = rope(k, positions, cfg.rope_theta, yarn)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B, KV, S_kv, Dh)

    new_cache, q_offset = None, 0
    if cache is not None:
        ck, cv = cache
        size = ck.shape[2]
        if window is not None and size <= window and s > 1:
            n = s if n_new is None else n_new
            ck, cv = ring_write(ck, k, clen, n), ring_write(cv, v, clen, n)
        else:
            ring = window is not None and size <= window
            wpos = jnp.mod(clen, size) if ring else clen
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), wpos, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), wpos, axis=2)
            k, v = ck, cv
            q_offset = jnp.minimum(clen, size - 1) if ring else clen
            window = None
        new_cache = (ck, cv)

    # fold heads into batch: q (B*H, S, Dh); k/v (B*KV, Skv, Dh)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    of = ops.attention(
        qf, k.reshape(b * kv, -1, dh), v.reshape(b * kv, -1, dh),
        causal=causal and memory is None,
        window=window if memory is None else None,
        q_offset=q_offset,
    )
    out = of.reshape(b, h, s, dh).transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# FFN: SwiGLU dense + sort-based MoE
# ---------------------------------------------------------------------------
def init_dense_ffn(key, cfg: ModelConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wg": _dense_init(ks[0], (d, f), dtype),
        "wu": _dense_init(ks[1], (d, f), dtype),
        "wd": _dense_init(ks[2], (f, d), dtype),
    }


def dense_ffn(x: jax.Array, p: Params) -> jax.Array:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def init_moe_ffn(key, cfg: ModelConfig, dtype) -> Params:
    """The router over all ``n_experts`` and the weights of the experts
    held here (``held_experts``)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        "wg": _dense_init(ks[1], (e, d, f), dtype),
        "wu": _dense_init(ks[2], (e, d, f), dtype),
        "wd": _dense_init(ks[3], (e, f, d), dtype),
    }


def route(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k routing over router logits (T, E): the softmax over all
    experts at the k largest, renormalized over them (the softmax of the k
    largest logits); returns (gates, experts), each (T, k)."""
    top, experts = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top, axis=-1), experts


def moe_ffn(x: jax.Array, p: Params, cfg: ModelConfig, *, first: int = 0,
            valid: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Dropless top-k mixture of experts over the experts this chip holds.

    x: (T, D) -> (T, D).  Each token is routed over all ``n_experts``:
    softmax of its router logits, the top k, renormalized over the k (the
    softmax of the k largest logits).  For every (token, expert) pair whose
    expert is held here, experts ``first`` .. ``first + held - 1`` with
    ``held`` the leading dim of ``p["wg"]``, the layer adds ``gate *
    (silu(x Wg_e) * (x Wu_e)) Wd_e``; a pair of another expert adds nothing
    here (its chip computes it).  The pairs are sorted by expert and each
    weight is one grouped product over them (``jax.lax.ragged_dot``): no
    capacity, no token dropped.  ``valid`` (T,) marks the tokens that route
    (False: padding).

    Returns ``(y, counts)``: counts (2,) int32 are the pairs computed and
    the held experts that received at least one token.
    """
    t, d = x.shape
    held, k = p["wg"].shape[0], cfg.top_k

    # the router is float32 by design: its product too (a TPU's default
    # would round its operands to bfloat16 and flip near-tied experts)
    logits = jnp.dot(x.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST)
    gates, experts = route(logits, k)
    local = experts - first
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine &= valid[:, None]

    key = jnp.where(mine, local, held).reshape(-1)  # (T*K,); not here -> held
    order = jnp.argsort(key)  # stable: by expert, then by token
    tok = order // k
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    kept = jnp.take(mine.reshape(-1), order)
    gate = jnp.where(kept, jnp.take(gates.reshape(-1), order), 0.0)
    with jax.named_scope("moe.experts"):
        xs = checkpoint_name(jnp.take(x, tok, axis=0), "moe_dispatch")
        hg = jax.lax.ragged_dot(xs, p["wg"], sizes)
        hu = jax.lax.ragged_dot(xs, p["wu"], sizes)
        y = jax.lax.ragged_dot((jax.nn.silu(hg) * hu).astype(x.dtype), p["wd"], sizes)
        y = checkpoint_name(y, "moe_expert_out")
    # rows past the held experts' groups belong to no product: zero them
    contrib = jnp.where(kept[:, None], y.astype(jnp.float32), 0.0) * gate[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib).astype(x.dtype)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out, counts


# ---------------------------------------------------------------------------
# Mamba block (selective SSM, chunked scan)
# ---------------------------------------------------------------------------
def init_mamba(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    din = cfg.mamba_expand * d
    n = cfg.mamba_d_state
    dt_rank = max(1, d // 16)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * din), dtype),
        "conv_w": _dense_init(ks[1], (cfg.mamba_d_conv, din), dtype, scale=0.5),
        "conv_b": jnp.zeros((din,), dtype),
        "x_proj": _dense_init(ks[2], (din, dt_rank + 2 * n), dtype),
        "dt_proj": _dense_init(ks[3], (dt_rank, din), dtype),
        "dt_bias": jnp.full((din,), -2.0, dtype),  # softplus -> small dt
        # a host constant: XLA's constant folding would round log differently
        "A_log": jnp.asarray(np.tile(np.log(np.arange(1, n + 1, dtype=np.float32)), (din, 1))),
        "Dskip": jnp.ones((din,), dtype),
        "out_proj": _dense_init(ks[4], (din, d), dtype),
    }


def _mamba_scan_chunked(dt, Bm, Cm, xc, A, h0, chunk: int):
    """Selective-SSM scan, chunked for the memory hierarchy.

    The (B, S, Din, N) tensors ``exp(dt*A)`` / ``dt*B*x`` are NEVER
    materialized over the full sequence: each lax.scan step computes them for
    one chunk only — (B, Q, Din, N) is the HBM working set — runs the
    associative scan within the chunk, contracts against C immediately
    (y = C·h), and carries only the (B, Din, N) state.  This is the a-priori
    working-set bounding the paper applies to L1, applied to HBM.

    dt, xc: (B, S, Din) fp32/bf16; Bm, Cm: (B, S, N); A: (Din, N).
    Returns y: (B, S, Din) fp32 and the final state (B, Din, N).
    """
    b, s, din = dt.shape
    n = A.shape[1]
    q = min(chunk, s)
    assert s % q == 0
    nchunks = s // q

    def resh(t):  # (B, S, ...) -> (nchunks, B, Q, ...)
        return t.reshape(b, nchunks, q, *t.shape[2:]).transpose(1, 0, 2, *range(3, t.ndim + 1))

    xs = (resh(dt), resh(Bm), resh(Cm), resh(xc))

    def chunk_step(h, inp):
        dtc, bc, cc, xcc = inp  # (B,Q,Din) / (B,Q,N) / (B,Q,N) / (B,Q,Din)
        a = jnp.exp(dtc[..., None] * A)  # (B,Q,Din,N) — chunk-local only
        bx = dtc[..., None] * bc[:, :, None, :] * xcc[..., None]

        def comb(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        bx = bx.at[:, 0].add(a[:, 0] * h)  # fold carry into first element
        _, hs = jax.lax.associative_scan(comb, (a, bx), axis=1)
        y = jnp.einsum("bqdn,bqn->bqd", hs, cc)  # contract C immediately
        return hs[:, -1], y

    h_last, ys = jax.lax.scan(chunk_step, h0, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, din)
    return y, h_last


def mamba(
    x: jax.Array, p: Params, cfg: ModelConfig,
    state: tuple[jax.Array, jax.Array] | None = None,
    chunk: int = 256,
):
    """x: (B, S, D). state = (conv_buf (B, d_conv-1, Din), h (B, Din, N))."""
    b, s, d = x.shape
    din = cfg.mamba_expand * d
    n = cfg.mamba_d_state
    dt_rank = max(1, d // 16)

    xz = x @ p["in_proj"]
    x1, z = jnp.split(xz, 2, axis=-1)  # (B, S, Din)

    # causal depthwise conv, optionally continuing from a state buffer
    dconv = cfg.mamba_d_conv
    if state is not None:
        conv_buf = state[0]
        x_pad = jnp.concatenate([conv_buf, x1], axis=1)
    else:
        x_pad = jnp.pad(x1, ((0, 0), (dconv - 1, 0), (0, 0)))
    new_conv_buf = x_pad[:, -(dconv - 1):, :] if dconv > 1 else jnp.zeros((b, 0, din), x1.dtype)
    xc = sum(
        x_pad[:, i : i + s, :] * p["conv_w"][i][None, None, :] for i in range(dconv)
    ) + p["conv_b"]
    xc = jax.nn.silu(xc)

    proj = xc @ p["x_proj"]  # (B, S, dt_rank + 2N)
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"] + p["dt_bias"])
    Bm = proj[..., dt_rank : dt_rank + n].astype(jnp.float32)
    Cm = proj[..., dt_rank + n :].astype(jnp.float32)

    A = -jnp.exp(p["A_log"])  # (Din, N)
    dtf = dt.astype(jnp.float32)
    xcf = xc.astype(jnp.float32)

    h0 = state[1] if state is not None else jnp.zeros((b, din, n), jnp.float32)
    # pad sequence to a chunk multiple (dt=0 => identity transition)
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        dtf = jnp.pad(dtf, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        xcf = jnp.pad(xcf, ((0, 0), (0, pad), (0, 0)))
    y, h_last = _mamba_scan_chunked(dtf, Bm, Cm, xcf, A, h0, q)
    y = y[:, :s].astype(x.dtype)
    y = y + p["Dskip"] * xc
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out, (new_conv_buf, h_last)


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg: ModelConfig, dtype) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, d), dtype),
        "wk": _dense_init(ks[1], (d, d), dtype),
        "wv": _dense_init(ks[2], (d, d), dtype),
        "wi": _dense_init(ks[3], (d, h), dtype, scale=0.01),
        "wf": _dense_init(ks[4], (d, h), dtype, scale=0.01),
        "bi": jnp.zeros((h,), dtype),
        "bf": jnp.full((h,), 3.0, dtype),  # forget-gate bias -> long memory
        "wo": _dense_init(ks[5], (d, d), dtype),
    }


def mlstm(
    x: jax.Array, p: Params, cfg: ModelConfig,
    state: tuple | None = None, chunk: int = 128,
):
    """Chunkwise-parallel mLSTM (matrix memory linear attention w/ gates).

    Stabilized in log space: within a chunk the decay matrix is computed
    from cumulative log-forget-gates; across chunks a (B, H, Dh, Dh) memory
    and (B, H, Dh) normalizer are carried.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h

    q = (x @ p["wq"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3) / math.sqrt(dh)
    k = (x @ p["wk"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    logf = jax.nn.log_sigmoid((x @ p["wf"] + p["bf"]).astype(jnp.float32))  # (B,S,H)
    logi = (x @ p["wi"] + p["bi"]).astype(jnp.float32)
    logf = logf.transpose(0, 2, 1)  # (B, H, S)
    logi = logi.transpose(0, 2, 1)

    qc = min(chunk, s)
    pad = (-s) % qc
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        logf = jnp.pad(logf, ((0, 0), (0, 0), (0, pad)))
        logi = jnp.pad(logi, ((0, 0), (0, 0), (0, pad)), constant_values=-1e30)
    S = q.shape[2]
    nch = S // qc

    def resh(t):
        return t.reshape(b, h, nch, qc, -1).transpose(2, 0, 1, 3, 4)

    qs, ks_, vs = resh(q), resh(k), resh(v)  # (nch, B, H, Q, Dh)
    lf = logf.reshape(b, h, nch, qc).transpose(2, 0, 1, 3)  # (nch, B, H, Q)
    li = logi.reshape(b, h, nch, qc).transpose(2, 0, 1, 3)

    if state is None:
        C0 = jnp.zeros((b, h, dh, dh), jnp.float32)
        n0 = jnp.zeros((b, h, dh), jnp.float32)
        m0 = jnp.full((b, h), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def step(carry, inp):
        C, n, m = carry
        qq, kk, vv, f, i_ = inp  # (B,H,Q,Dh) / (B,H,Q)
        F = jnp.cumsum(f, axis=-1)  # cumulative log-forget within chunk
        logd_inter = F + m[..., None]  # decay applied to carried memory
        # intra-chunk decay matrix: D[t,s] = F_t - F_s + i_s  (s <= t)
        Dm = F[..., :, None] - F[..., None, :] + i_[..., None, :]
        tri = jnp.tril(jnp.ones((qq.shape[2], qq.shape[2]), bool))
        Dm = jnp.where(tri, Dm, -1e30)
        m_intra = jnp.max(Dm, axis=-1)  # (B,H,Q)
        m_new = jnp.maximum(logd_inter, m_intra)  # (B,H,Q) running stabilizer
        sc_inter = jnp.exp(logd_inter - m_new)  # (B,H,Q)
        P = jnp.exp(Dm - m_new[..., None])  # (B,H,Q,Q)
        y_intra = jnp.einsum(
            "bhts,bhsd->bhtd",
            P * jnp.einsum("bhtd,bhsd->bhts", qq.astype(jnp.float32), kk.astype(jnp.float32)),
            vv.astype(jnp.float32),
        )
        y_inter = sc_inter[..., None] * jnp.einsum(
            "bhtd,bhde->bhte", qq.astype(jnp.float32), C
        )
        norm = jnp.einsum(
            "bhts,bhts->bht",
            P, jnp.einsum("bhtd,bhsd->bhts", qq.astype(jnp.float32), kk.astype(jnp.float32)),
        ) + sc_inter * jnp.einsum("bhtd,bhd->bht", qq.astype(jnp.float32), n)
        denom = jnp.maximum(jnp.abs(norm), jnp.exp(-m_new))
        out = (y_intra + y_inter) / denom[..., None]

        # chunk-final state update
        Ftot = F[..., -1:]  # (B,H,1)
        m_next = jnp.maximum(Ftot[..., 0] + m, jnp.max(Ftot - F + i_, axis=-1))
        w_src = jnp.exp(Ftot - F + i_ - m_next[..., None])  # (B,H,Q)
        C_new = jnp.exp(Ftot[..., 0] + m - m_next)[..., None, None] * C + jnp.einsum(
            "bhs,bhsd,bhse->bhde", w_src, kk.astype(jnp.float32), vv.astype(jnp.float32)
        )
        n_new = jnp.exp(Ftot[..., 0] + m - m_next)[..., None] * n + jnp.einsum(
            "bhs,bhsd->bhd", w_src, kk.astype(jnp.float32)
        )
        return (C_new, n_new, m_next), out

    (Cf, nf, mf), ys = jax.lax.scan(step, (C0, n0, m0), (qs, ks_, vs, lf, li))
    ys = ys.transpose(1, 2, 0, 3, 4).reshape(b, h, S, dh)[:, :, :s]
    out = ys.transpose(0, 2, 1, 3).reshape(b, s, d).astype(x.dtype)
    return out @ p["wo"], (Cf, nf, mf)


def init_slstm(key, cfg: ModelConfig, dtype) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "wz": _dense_init(ks[0], (d, d), dtype),
        "wi": _dense_init(ks[1], (d, d), dtype, scale=0.01),
        "wf": _dense_init(ks[2], (d, d), dtype, scale=0.01),
        "wo_gate": _dense_init(ks[3], (d, d), dtype, scale=0.01),
        "bf": jnp.full((d,), 3.0, dtype),
        "wo": _dense_init(ks[4], (d, d), dtype),
    }


def slstm(x: jax.Array, p: Params, cfg: ModelConfig, state=None):
    """Stabilized sLSTM: genuinely sequential scalar recurrence (lax.scan).

    This is the normalizer's 'recurrence' idiom class: the time iterator is
    a loop-carried SCC that fission must keep atomic.
    """
    b, s, d = x.shape
    z = jnp.tanh(x @ p["wz"]).astype(jnp.float32)
    i_ = (x @ p["wi"]).astype(jnp.float32)
    f_ = (x @ p["wf"] + p["bf"]).astype(jnp.float32)
    o_ = jax.nn.sigmoid((x @ p["wo_gate"]).astype(jnp.float32))

    if state is None:
        c0 = jnp.zeros((b, d), jnp.float32)
        n0 = jnp.zeros((b, d), jnp.float32)
        m0 = jnp.full((b, d), -1e30, jnp.float32)
    else:
        c0, n0, m0 = state

    def step(carry, inp):
        c, n, m = carry
        zt, it, ft, ot = inp
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        ig = jnp.exp(it - m_new)
        fg = jnp.exp(logf + m - m_new)
        c_new = fg * c + ig * zt
        n_new = fg * n + ig
        y = ot * c_new / jnp.maximum(jnp.abs(n_new), 1.0)
        return (c_new, n_new, m_new), y

    (cf, nf, mf), ys = jax.lax.scan(
        step, (c0, n0, m0),
        (z.transpose(1, 0, 2), i_.transpose(1, 0, 2),
         f_.transpose(1, 0, 2), o_.transpose(1, 0, 2)),
    )
    out = ys.transpose(1, 0, 2).astype(x.dtype) @ p["wo"]
    return out, (cf, nf, mf)
