"""Plain reference of a dense decoder of the Llama/Mistral family (as
H2O-Danube3 is, arXiv 2407.09276), in ``jax.numpy`` and float32.

Written from the published layer equations, independent of the model code
under test: token embedding; per layer, RMSNorm, grouped-query causal
self-attention with rotary position embedding (the two halves of each head
rotated against each other, frequencies ``theta ** (-2i / d_head)``) and an
optional sliding window, a residual add, RMSNorm, a SwiGLU feed-forward
``(silu(x Wg) * (x Wu)) Wd`` and a residual add; a final RMSNorm and the
output head.  It takes the served parameter arrays as they are (layer
stacks under ``layers``: ``norm1``, ``mixer`` ``wq wk wv wo``, ``norm2``,
``ffn`` ``wg wu wd``; ``embed``, ``final_norm``, ``lm_head``) and upcasts
them one layer at a time, and computes attention by blocks of key-value
heads, so that it fits on the chip beside the served model.

The check that decides ``correct`` calls it under
``jax.default_matmul_precision("highest")``.  Its options are the control
and the faults that the limits are set against (``bench/calibrate.py``):
``quant="int8"`` rounds both operands of every weight product to int8
(weights per output column, activations per row), ``skip_layer`` leaves a
layer out, ``positions`` places tokens elsewhere for the rotary embedding,
and ``window`` overrides the configuration's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

KV_HEADS_PER_BLOCK = 2
_CONFIGURED = object()


def _int8(x, axis):
    """``x`` rounded to int8 steps of its largest magnitude along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, quant):
    if quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    return x @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, H, Dh); pos: (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


@partial(jax.jit, static_argnames=("shape", "window", "quant"))
def _layer(x, lp, pos, *, shape, window, quant):
    n_heads, n_kv, dh, eps, theta = shape
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    s = x.shape[0]
    h = _rmsnorm(x, lp["norm1"], eps)
    q = _rope(_mm(h, lp["mixer"]["wq"], quant).reshape(s, n_heads, dh), pos, theta)
    k = _rope(_mm(h, lp["mixer"]["wk"], quant).reshape(s, n_kv, dh), pos, theta)
    v = _mm(h, lp["mixer"]["wv"], quant).reshape(s, n_kv, dh)
    i = jnp.arange(s)
    visible = i[None, :] <= i[:, None]
    if window is not None:
        visible &= (i[:, None] - i[None, :]) < window
    group = n_heads // n_kv
    outs = []
    for g0 in range(0, n_kv, KV_HEADS_PER_BLOCK):
        kb = k[:, g0:g0 + KV_HEADS_PER_BLOCK]  # (S, Gk, Dh)
        vb = v[:, g0:g0 + KV_HEADS_PER_BLOCK]
        qb = q[:, g0 * group:(g0 + KV_HEADS_PER_BLOCK) * group]
        qb = qb.reshape(s, -1, group, dh)  # (S, Gk, group, Dh)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, kb) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(visible, scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", w, vb).reshape(s, -1))
    x = x + _mm(jnp.concatenate(outs, axis=-1), lp["mixer"]["wo"], quant)
    h = _rmsnorm(x, lp["norm2"], eps)
    f = jax.nn.silu(_mm(h, lp["ffn"]["wg"], quant)) * _mm(h, lp["ffn"]["wu"], quant)
    return x + _mm(f, lp["ffn"]["wd"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, w, *, eps, quant):
    h = _rmsnorm(x, g.astype(jnp.float32), eps)
    return _mm(h, w.astype(jnp.float32), quant)


def reference(params, tokens, model: dict, *, positions=None, window=_CONFIGURED,
              skip_layer: int | None = None, quant: str | None = None):
    """Logits (S, V) in float32 of the token sequence ``tokens`` (S,), each
    position attending causally to those before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0]) if positions is None else jnp.asarray(positions)
    if window is _CONFIGURED:
        window = model.get("window")
    shape = (model["n_heads"], model["n_kv_heads"], model["d_head"],
             float(model["norm_eps"]), float(model["rope_theta"]))
    x = params["embed"][tokens].astype(jnp.float32)
    stack = params["layers"]
    for layer in range(model["n_layers"]):
        if layer == skip_layer:
            continue
        lp = jax.tree_util.tree_map(lambda a: a[layer], stack)
        x = _layer(x, lp, pos, shape=shape, window=window, quant=quant)
    return _head(x, params["final_norm"], params["lm_head"],
                 eps=float(model["norm_eps"]), quant=quant)
