"""Plain reference of Mellum2-12B-A2.5B (JetBrains/Mellum2-12B-A2.5B-Instruct,
``config.json``), in ``jax.numpy`` and float32, for the share of its experts
that one chip holds.

Written from the published configuration, independent of the model code
under test: token embedding; per layer, RMSNorm, grouped-query causal
self-attention (32 query heads over 4 key-value heads of 128, no bias) with
rotary position embedding (the two halves of each head rotated against each
other), a residual add, RMSNorm, the expert layer and a residual add; a final
RMSNorm and the untied output head.  ``layer_types`` repeats three sliding
layers (a query sees the ``window`` latest positions, itself included) and
one full layer.  Sliding layers take plain RoPE, frequencies ``theta **
(-2i / d_head)``; full layers take YaRN (Hugging Face
``_compute_yarn_parameters``): the correction range ``floor``/``ceil`` of
``d ln(original_max / (beta 2 pi)) / (2 ln theta)`` for ``beta_fast`` and
``beta_slow``, a linear ramp over it from the plain frequencies to the same
divided by ``factor``, and cos and sin multiplied by ``attention_factor``.
The expert layer routes each token over all ``n_experts``: softmax of the
router logits, the ``top_k`` largest, renormalized over the k; of the chosen
experts, those held here (``0 .. experts_held - 1``) each add ``gate *
(silu(x Wg) * (x Wu)) Wd``, and the others add nothing (their chips compute
them).  No QK normalization and no MTP module: the configuration declares
neither.

It takes the served parameter arrays as they are (one stack per position
of the ``layer_types`` period under ``periods``, layer ``l`` at index ``l //
period`` of stack ``l % period``: ``norm1``, ``mixer`` ``wq wk wv wo``,
``norm2``, ``ffn`` ``router wg wu wd``, the expert weights of the held
experts only; ``embed``, ``final_norm``, ``lm_head``), upcasts them
one layer at a time, computes attention by blocks of query rows and one
key-value head at a time and the head by blocks of the vocabulary, so that
it fits on the chip beside the served model at 9216 positions.

The check that decides ``correct`` calls it under
``jax.default_matmul_precision("highest")``.  Its options are the control
and the faults that the limits are set against (``bench/calibrate.py``):
``quant="fp8"`` rounds both operands of every weight product to float8
(e4m3), ``quant="int8"`` to int8 steps (scaled per output column of the
weights, per row of the activations); ``window=None`` drops the
window of the sliding layers; ``yarn=False`` gives the full layers plain
RoPE; ``drop_expert`` leaves one held expert out; ``renormalize=False``
keeps the softmax over all experts at the chosen k; ``positions`` places
tokens elsewhere for the rotary embedding.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024
VOCAB_BLOCK = 16384
_CONFIGURED = object()


def _int8(x, axis):
    """``x`` rounded to int8 steps of its largest magnitude along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x, axis):
    """``x`` rounded to float8 (e4m3), scaled so that its largest magnitude
    along ``axis`` is e4m3's largest (448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant is not None:
        q = {"int8": _int8, "fp8": _fp8}[quant]
        x, w = q(x, -1), q(w, 0)
    return x @ w


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _frequencies(dh: int, theta: float, yarn: tuple | None) -> tuple[np.ndarray, float]:
    """Inverse frequencies (dh/2,) and the scale of cos and sin."""
    plain = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    if yarn is None:
        return plain.astype(np.float32), 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def dim(rotations):
        return dh * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # share of the plain frequency
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32), attention_factor


def _rope(x, pos, freq, scale):
    """x: (S, H, Dh); pos: (S,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window):
    """Causal attention of q (S, H, Dh) over k, v (S, KV, Dh), by blocks of
    query rows and one key-value head at a time; ``window`` bounds how far
    back a query sees (None: every earlier position)."""
    s, h, dh = q.shape
    kv = k.shape[1]
    group = h // kv
    nb = -(-s // ROW_BLOCK)
    qp = jnp.pad(q, ((0, nb * ROW_BLOCK - s), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def block(b):
        rows = b * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        visible = keys[None, :] <= rows[:, None]
        if window is not None:
            visible &= (rows[:, None] - keys[None, :]) < window
        qb = jax.lax.dynamic_slice_in_dim(qp, b * ROW_BLOCK, ROW_BLOCK)
        outs = []
        for g in range(kv):
            qg = qb[:, g * group:(g + 1) * group]  # (B, group, Dh)
            scores = jnp.einsum("qrd,kd->rqk", qg, k[:, g]) / jnp.sqrt(jnp.float32(dh))
            w = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("rqk,kd->qrd", w, v[:, g]))
        return jnp.concatenate(outs, axis=1)  # (B, H, Dh)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * ROW_BLOCK, h * dh)[:s]


def _experts(h, ffn, k, held, quant, drop_expert, renormalize):
    """The held experts' part of the expert layer for the tokens h (S, D)."""
    probs = jax.nn.softmax(h @ ffn["router"], axis=-1)  # over every expert
    top, chosen = jax.lax.top_k(probs, k)
    if renormalize:
        top = top / top.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(held):
        if e == drop_expert:
            continue
        gate = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)  # (S,)
        f = jax.nn.silu(_mm(h, ffn["wg"][e], quant)) * _mm(h, ffn["wu"][e], quant)
        y = y + gate[:, None] * _mm(f, ffn["wd"][e], quant)
    return y


@partial(jax.jit, static_argnames=("shape", "window", "yarn", "quant", "drop_expert",
                                   "renormalize"))
def _layer(x, lp, pos, *, shape, window, yarn, quant, drop_expert, renormalize):
    n_heads, n_kv, dh, eps, theta, k, held = shape
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    s = x.shape[0]
    freq, scale = _frequencies(dh, theta, yarn)
    h = _rmsnorm(x, lp["norm1"], eps)
    q = _rope(_mm(h, lp["mixer"]["wq"], quant).reshape(s, n_heads, dh), pos, freq, scale)
    kk = _rope(_mm(h, lp["mixer"]["wk"], quant).reshape(s, n_kv, dh), pos, freq, scale)
    v = _mm(h, lp["mixer"]["wv"], quant).reshape(s, n_kv, dh)
    x = x + _mm(_attention(q, kk, v, window), lp["mixer"]["wo"], quant)
    h = _rmsnorm(x, lp["norm2"], eps)
    return x + _experts(h, lp["ffn"], k, held, quant, drop_expert, renormalize)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, w, *, eps, quant):
    h = _rmsnorm(x, g.astype(jnp.float32), eps)
    v = w.shape[1]
    out = jnp.zeros((x.shape[0], v), jnp.float32)
    for c in range(0, v, VOCAB_BLOCK):
        out = out.at[:, c:c + VOCAB_BLOCK].set(
            _mm(h, w[:, c:c + VOCAB_BLOCK].astype(jnp.float32), quant))
    return out


def reference(params, tokens, model: dict, *, positions=None, window=_CONFIGURED,
              yarn: bool = True, drop_expert: int | None = None, renormalize: bool = True,
              quant: str | None = None):
    """Logits (S, V) in float32 of the token sequence ``tokens`` (S,), each
    position attending causally to those before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0]) if positions is None else jnp.asarray(positions)
    if window is _CONFIGURED:
        window = model["window"]
    y = model["yarn"]
    yarn_params = (float(y["factor"]), float(y["original_max_position_embeddings"]),
                   float(y["beta_fast"]), float(y["beta_slow"]),
                   float(y["attention_factor"])) if yarn else None
    shape = (model["n_heads"], model["n_kv_heads"], model["d_head"], float(model["norm_eps"]),
             float(model["rope_theta"]), model["top_k"], model["experts_held"])
    kinds = list(model["layer_types"])
    x = params["embed"][tokens].astype(jnp.float32)
    for layer in range(model["n_layers"]):
        period, at = divmod(layer, len(kinds))
        lp = jax.tree_util.tree_map(lambda a: a[period], params["periods"][at])
        sliding = kinds[at] == "sliding"
        x = _layer(x, lp, pos, shape=shape, window=window if sliding else None,
                   yarn=None if sliding else yarn_params, quant=quant,
                   drop_expert=drop_expert, renormalize=renormalize)
    return _head(x, params["final_norm"], params["lm_head"],
                 eps=float(model["norm_eps"]), quant=quant)
