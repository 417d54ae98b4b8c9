"""Model assembly: init / forward / decode for all 10 assigned architectures.

Families:
  dense / moe / vlm  — decoder-only transformer (GQA, SWA, optional QKV bias,
                       optional MoE FFN), layers run under ``lax.scan`` over
                       stacked parameters (compile once per unique layer).
  audio              — encoder-decoder (stub frame embeddings -> encoder;
                       text decoder with cross-attention).
  hybrid (Jamba)     — periodic layer pattern (1 attention : 7 Mamba, MoE on
                       alternate layers); scanned over periods.
  ssm (xLSTM)        — periodic mLSTM/sLSTM pattern, no FFN.

Frontends ([vlm]/[audio]) are STUBS per the assignment: ``input_specs()``
supplies precomputed patch/frame embeddings.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, kind: str, use_moe: bool, dt) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if kind == "attn":
        p["mixer"] = L.init_attention(ks[0], cfg, dt)
    elif kind == "mamba":
        p["mixer"] = L.init_mamba(ks[0], cfg, dt)
    elif kind == "mlstm":
        p["mixer"] = L.init_mlstm(ks[0], cfg, dt)
    elif kind == "slstm":
        p["mixer"] = L.init_slstm(ks[0], cfg, dt)
    if cfg.d_ff:
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = (
            L.init_moe_ffn(ks[1], cfg, dt) if use_moe else L.init_dense_ffn(ks[1], cfg, dt)
        )
    return p


@partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random parameters for ``cfg`` from ``key``.

    Each layer stack is one ``lax.map`` of the block init over its layer
    keys, under jit: only the stacked tree materialises on the device (one
    copy of the weights, never a per-layer list beside it), one layer's
    float32 draws are live at a time, and the program compiles once per
    block kind rather than once per layer.
    """
    dt = _dtype(cfg)
    keys = jax.random.split(key, cfg.n_layers + cfg.enc_layers + 4)
    p: Params = {
        "embed": (L.normal(keys[-1], (cfg.vocab, cfg.d_model)) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        # a multiply, not a divide: XLA rewrites a division by a constant
        # under jit, which would round differently from eager execution
        p["lm_head"] = (L.normal(keys[-2], (cfg.d_model, cfg.vocab))
                        * (1.0 / math.sqrt(cfg.d_model))).astype(dt)

    if cfg.family in ("dense", "moe", "vlm"):
        # scan needs identical treedefs: every layer is layer 0's kind
        p["layers"] = jax.lax.map(
            lambda k: _init_block(k, cfg, "attn", cfg.layer_is_moe(0), dt),
            keys[:cfg.n_layers])
    elif cfg.family == "audio":
        def dec_block(k):
            blk = _init_block(k, cfg, "attn", False, dt)
            blk["norm_x"] = jnp.ones((cfg.d_model,), dt)
            blk["cross"] = L.init_attention(jax.random.fold_in(k, 7), cfg, dt)
            return blk

        p["encoder"] = jax.lax.map(
            lambda k: _init_block(k, cfg, "attn", False, dt), keys[:cfg.enc_layers])
        p["decoder"] = jax.lax.map(
            dec_block, keys[cfg.enc_layers:cfg.enc_layers + cfg.n_layers])
        p["enc_final_norm"] = jnp.ones((cfg.d_model,), dt)
    elif cfg.family in ("hybrid", "ssm"):
        # layer g * period + pos is stacked at index g of position pos
        period = cfg.attn_period if cfg.family == "hybrid" else len(cfg.block_pattern)
        n_periods = cfg.n_layers // period
        grid = keys[:n_periods * period].reshape(n_periods, period, *keys.shape[1:])
        p["periods"] = [
            jax.lax.map(lambda k, pos=pos: _init_block(
                k, cfg, cfg.layer_kind(pos), cfg.layer_is_moe(pos), dt), grid[:, pos])
            for pos in range(period)
        ]
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# block application (sequence form)
# ---------------------------------------------------------------------------
def _apply_block(
    x, blk: Params, cfg: ModelConfig, kind: str, use_moe: bool,
    positions, causal=True, memory=None,
    state=None, write_pos=0, attn_offset=0,
):
    """Returns (x_out, new_state)."""
    from ..kernels import ops

    sp = cfg.seq_parallel and x.shape[1] > 1

    def _sp(t):
        # Megatron SP: sub-block outputs reduce-scatter onto the sequence dim
        # (1x ring bytes); the next column-parallel matmul all-gathers.
        return L.constrain(t, ("pod", "data"), "model", None) if sp else t

    normed = ops.rmsnorm(x, blk["norm1"], eps=cfg.norm_eps)
    new_state = None
    if kind == "attn":
        cache = state
        att, new_state = L.attention(
            normed, blk["mixer"], cfg, positions=positions, causal=causal,
            cache=cache, write_pos=write_pos, attn_offset=attn_offset,
            memory=None,
        )
        x = x + _sp(att)
        if memory is not None:  # cross-attention sub-block (enc-dec decoder)
            normed_x = ops.rmsnorm(x, blk["norm_x"], eps=cfg.norm_eps)
            cross, _ = L.attention(
                normed_x, blk["cross"], cfg, positions=positions,
                causal=False, memory=memory,
            )
            x = x + cross
    elif kind == "mamba":
        out, new_state = L.mamba(normed, blk["mixer"], cfg, state=state)
        x = x + out
    elif kind == "mlstm":
        out, new_state = L.mlstm(normed, blk["mixer"], cfg, state=state)
        x = x + out
    elif kind == "slstm":
        out, new_state = L.slstm(normed, blk["mixer"], cfg, state=state)
        x = x + out
    if cfg.d_ff:
        normed2 = ops.rmsnorm(x, blk["norm2"], eps=cfg.norm_eps)
        if use_moe:
            b, s, d = normed2.shape
            y = L.moe_ffn(normed2.reshape(b * s, d), blk["ffn"], cfg).reshape(b, s, d)
        else:
            y = L.dense_ffn(normed2, blk["ffn"])
        x = x + _sp(y)
    return x, new_state


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------
def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    from ..kernels import ops

    x = ops.rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


def forward(cfg: ModelConfig, params: Params, batch: dict[str, jax.Array]) -> jax.Array:
    """batch: tokens (B, S) [+ 'embeds' (B, Sf, D) for vlm/audio frontends].

    Returns logits (B, S_text, V).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    n_front = 0
    if cfg.frontend is not None and cfg.family == "vlm":
        emb = batch["embeds"].astype(x.dtype)  # precomputed patch embeddings
        n_front = emb.shape[1]
        x = jnp.concatenate([emb, x], axis=1)
    x = L.constrain(x, ("pod", "data"), None, None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :].repeat(b, 0)

    def _maybe_remat(fn):
        if cfg.remat == "block":
            return jax.checkpoint(fn)
        if cfg.remat == "block_save_moe":
            # keep the MoE dispatch/expert outputs across the backward: the
            # EP collectives then run once instead of thrice
            policy = jax.checkpoint_policies.save_only_these_names(
                "moe_dispatch", "moe_expert_out"
            )
            return jax.checkpoint(fn, policy=policy)
        return fn

    if cfg.family in ("dense", "moe", "vlm"):
        is_moe = cfg.layer_is_moe(0)

        @_maybe_remat
        def body(xc, blk):
            out, _ = _apply_block(xc, blk, cfg, "attn", is_moe, positions)
            return out, None

        x, _ = jax.lax.scan(body, x, params["layers"])
    elif cfg.family == "audio":
        memory = encode(cfg, params, batch["embeds"])

        @_maybe_remat
        def body(xc, blk):
            out, _ = _apply_block(xc, blk, cfg, "attn", False, positions, memory=memory)
            return out, None

        x, _ = jax.lax.scan(body, x, params["decoder"])
    elif cfg.family in ("hybrid", "ssm"):
        period_params = params["periods"]
        kinds = [cfg.layer_kind(pos) for pos in range(len(period_params))]
        moes = [cfg.layer_is_moe(pos) for pos in range(len(period_params))]

        if cfg.remat == "layer":
            # per-position remat: during the period backward only ONE
            # layer's intermediates are live (vs all 8 with period remat)
            def apply_pos(xc, blk, pos):
                return _apply_block(xc, blk, cfg, kinds[pos], moes[pos], positions)[0]

            apply_pos = jax.checkpoint(apply_pos, static_argnums=(2,))

            def body(xc, blks):
                for pos, blk in enumerate(blks):
                    xc = apply_pos(xc, blk, pos)
                return xc, None
        else:
            @_maybe_remat
            def body(xc, blks):
                for pos, blk in enumerate(blks):
                    xc, _ = _apply_block(xc, blk, cfg, kinds[pos], moes[pos], positions)
                return xc, None

        x, _ = jax.lax.scan(body, x, tuple(period_params))
    logits = _logits(cfg, params, x)
    if n_front:
        logits = logits[:, n_front:, :]
    return logits


def encode(cfg: ModelConfig, params: Params, embeds: jax.Array) -> jax.Array:
    """Audio encoder over precomputed frame embeddings (bidirectional)."""
    from ..kernels import ops

    b = embeds.shape[0]
    positions = jnp.arange(embeds.shape[1], dtype=jnp.int32)[None, :].repeat(b, 0)

    def body(xc, blk):
        out, _ = _apply_block(xc, blk, cfg, "attn", False, positions, causal=False)
        return out, None

    x, _ = jax.lax.scan(body, embeds, params["encoder"])
    return ops.rmsnorm(x, params["enc_final_norm"], eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------
def _empty_attn_cache(cfg: ModelConfig, b: int, s_max: int, dt, ring: bool) -> tuple:
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    # SWA archs only ever attend to the last `window` positions: with
    # ring=True the cache is a window-sized ring buffer (the sub-quadratic
    # long-context path); ring=False allocates the full length (serve engine
    # prefill convenience).
    eff = min(s_max, cfg.window) if (ring and cfg.window) else s_max
    shape = (b, eff, kv, dh)
    return (jnp.zeros(shape, dt), jnp.zeros(shape, dt))


def _empty_state(cfg: ModelConfig, kind: str, b: int, s_max: int, dt, ring: bool = True):
    d = cfg.d_model
    if kind == "attn":
        return _empty_attn_cache(cfg, b, s_max, dt, ring)
    if kind == "mamba":
        din = cfg.mamba_expand * d
        return (
            jnp.zeros((b, cfg.mamba_d_conv - 1, din), dt),
            jnp.zeros((b, din, cfg.mamba_d_state), jnp.float32),
        )
    if kind == "mlstm":
        h = cfg.n_heads
        dh = d // h
        return (
            jnp.zeros((b, h, dh, dh), jnp.float32),
            jnp.zeros((b, h, dh), jnp.float32),
            jnp.full((b, h), -1e30, jnp.float32),
        )
    if kind == "slstm":
        return (
            jnp.zeros((b, d), jnp.float32),
            jnp.zeros((b, d), jnp.float32),
            jnp.full((b, d), -1e30, jnp.float32),
        )
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, b: int, s_max: int, ring: bool = True) -> dict:
    dt = _dtype(cfg)
    state: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        st = _empty_state(cfg, "attn", b, s_max, dt, ring)
        state["layers"] = jax.tree_util.tree_map(
            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), st
        )
    elif cfg.family == "audio":
        st = _empty_state(cfg, "attn", b, s_max, dt, ring)
        state["layers"] = jax.tree_util.tree_map(
            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), st
        )
        state["memory"] = jnp.zeros((b, cfg.frontend_len, cfg.d_model), dt)
    elif cfg.family in ("hybrid", "ssm"):
        period = cfg.attn_period or len(cfg.block_pattern)
        n_periods = cfg.n_layers // period
        per_pos = []
        for pos in range(period):
            st = _empty_state(cfg, cfg.layer_kind(pos), b, s_max, dt, ring)
            per_pos.append(
                jax.tree_util.tree_map(
                    lambda x: jnp.zeros((n_periods,) + x.shape, x.dtype), st
                )
            )
        state["periods"] = per_pos
    return state


def decode_step(
    cfg: ModelConfig, params: Params, state: dict, tokens: jax.Array
) -> tuple[jax.Array, dict]:
    """Decode/prefill step: tokens (B, s) -> logits (B, s, V) + new state.

    s == 1 is the serve decode step; s > 1 prefills the cache (requires a
    full-length, non-ring cache — the serve engine allocates ring=False).
    """
    b, s = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    clen = state["len"]
    positions = clen + jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    new_state = dict(state)

    # SWA ring buffer: write slot wraps at the cache size; not-yet-written
    # slots are masked because attn_offset caps the causal test
    def _slots(kind: str, s_cache: int):
        if kind != "attn":
            return 0, 0
        ring = cfg.window is not None and s_cache <= cfg.window
        if ring:
            # ring caches decode one token at a time
            return jnp.mod(clen, s_cache), jnp.minimum(clen, s_cache - 1)
        return clen, clen

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        layer_params = params["layers"] if cfg.family != "audio" else params["decoder"]
        memory = state.get("memory")
        is_moe = cfg.layer_is_moe(0)
        wpos, aoff = _slots("attn", state["layers"][0].shape[2])

        def body(xc, inp):
            blk, cache = inp
            out, new_cache = _apply_block(
                xc, blk, cfg, "attn", is_moe, positions,
                state=cache, write_pos=wpos, attn_offset=aoff, memory=memory,
            )
            return out, new_cache

        x, caches = jax.lax.scan(body, x, (layer_params, state["layers"]))
        new_state["layers"] = caches
    else:
        period_params = params["periods"]
        period = len(period_params)
        kinds = [cfg.layer_kind(pos) for pos in range(period)]
        moes = [cfg.layer_is_moe(pos) for pos in range(period)]

        def body(xc, inp):
            blks, sts = inp  # tuples over positions, sliced per period
            new_sts = []
            for pos in range(period):
                sc = sts[pos][0].shape[1] if kinds[pos] == "attn" else 0
                wpos, aoff = _slots(kinds[pos], sc)
                xc, nst = _apply_block(
                    xc, blks[pos], cfg, kinds[pos], moes[pos], positions,
                    state=sts[pos], write_pos=wpos, attn_offset=aoff,
                )
                new_sts.append(nst)
            return xc, tuple(new_sts)

        x, new_per = jax.lax.scan(
            body, x, (tuple(period_params), tuple(state["periods"]))
        )
        new_state["periods"] = list(new_per)

    new_state["len"] = clen + s
    return _logits(cfg, params, x), new_state


# ---------------------------------------------------------------------------
# slot-batched decode (the continuous-batching serve path)
# ---------------------------------------------------------------------------
def init_slot_states(cfg: ModelConfig, n_slots: int, s_max: int) -> dict:
    """Decode states for ``n_slots`` independent request slots, stacked on a
    leading slot axis (each slot is a ``b=1``, ``ring=False`` decode state
    with its own ``len`` scalar).  The serving engine writes a freshly
    prefilled request into one slot with ``write_slot`` while the others are
    mid-stream."""
    st = init_decode_state(cfg, 1, s_max, ring=False)
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((n_slots,) + x.shape, x.dtype), st
    )


def write_slot(states: dict, i: int, state: dict) -> dict:
    """Insert a single-slot (``b=1``) decode state at slot index ``i`` of a
    slot-stacked state tree (a refill: the new request's prefilled cache and
    length replace whatever the finished request left behind)."""
    return jax.tree_util.tree_map(lambda s, x: s.at[i].set(x), states, state)


def decode_slots(
    cfg: ModelConfig, params: Params, states: dict, tokens: jax.Array
) -> tuple[jax.Array, dict]:
    """One decode step for every slot at once.

    ``states`` is a slot-stacked tree (``init_slot_states``); ``tokens`` is
    ``(N,)`` int32 — the last sampled token per slot.  Returns
    ``(logits (N, V), new states)``.  Each slot advances at its own cache
    length / write offset (``vmap`` over the slot axis), which is what lets
    a freshly admitted request coexist with half-finished ones without any
    retrace: the traced shapes depend only on ``(N, s_max)``.
    """

    def one(state, tok):
        logits, st = decode_step(cfg, params, state, tok.reshape(1, 1))
        return logits[0, -1], st

    return jax.vmap(one)(states, tokens)


def decode_slots_greedy(
    cfg: ModelConfig, params: Params, states: dict, tokens: jax.Array
) -> tuple[jax.Array, dict]:
    """``decode_slots`` with the greedy sample fused on device: returns
    ``((N,) int32 next tokens, new states)``.  Keeping the argmax on device
    means the sampled tokens can feed the *next* dispatched step directly —
    the engine's pipelined dispatch only blocks on them at harvest points."""
    logits, states = decode_slots(cfg, params, states, tokens)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), states
