"""Model assembly: init / forward / decode for all 10 assigned architectures.

Families:
  dense / moe / vlm  — decoder-only transformer (GQA, SWA, optional QKV bias,
                       optional MoE FFN), layers run under ``lax.scan`` over
                       stacked parameters (compile once per unique layer);
                       a repeating pattern of attention kinds
                       (``layer_types``: sliding or full) is scanned over
                       periods, one stack per position.
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
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Yarn
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

    if cfg.family == "audio":
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
    elif cfg.period:
        # layer g * period + pos is stacked at index g of position pos
        period = cfg.period
        n_periods = cfg.n_layers // period
        grid = keys[:n_periods * period].reshape(n_periods, period, *keys.shape[1:])
        p["periods"] = [
            jax.lax.map(lambda k, pos=pos: _init_block(
                k, cfg, cfg.layer_kind(pos), cfg.layer_is_moe(pos), dt), grid[:, pos])
            for pos in range(period)
        ]
    else:
        # scan needs identical treedefs: every layer is layer 0's kind
        p["layers"] = jax.lax.map(
            lambda k: _init_block(k, cfg, "attn", cfg.layer_is_moe(0), dt),
            keys[:cfg.n_layers])
    return p


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
class Spec(NamedTuple):
    """What a layer position computes: its mixer, whether its FFN is the
    expert layer, its attention window and its rotary scaling."""

    kind: str
    moe: bool
    window: int | None
    yarn: Yarn | None


def layer_spec(cfg: ModelConfig, l: int) -> Spec:
    return Spec(cfg.layer_kind(l), cfg.layer_is_moe(l), cfg.layer_window(l), cfg.layer_yarn(l))


def stack_specs(cfg: ModelConfig) -> list[Spec]:
    """The spec of each parameter stack the layers scan over: one per
    position of the period, or, for a single stack, layer 0's (every layer
    shares its structure)."""
    if cfg.period:
        return [layer_spec(cfg, pos) for pos in range(cfg.period)]
    return [layer_spec(cfg, 0)._replace(moe=cfg.layer_is_moe(0) and cfg.family != "audio")]


def _param_stacks(cfg: ModelConfig, params: Params) -> list:
    if cfg.period:
        return list(params["periods"])
    return [params["decoder" if cfg.family == "audio" else "layers"]]


def _state_stacks(cfg: ModelConfig, state: dict) -> list:
    return list(state["periods"]) if cfg.period else [state["layers"]]


def _with_stacks(cfg: ModelConfig, state: dict, stacks) -> dict:
    if cfg.period:
        return dict(state, periods=list(stacks))
    return dict(state, layers=stacks[0])


def _cross(x, blk: Params, cfg: ModelConfig, positions, memory):
    """The cross-attention sub-block of an encoder-decoder's decoder layer,
    residual included."""
    from ..kernels import ops

    normed = ops.rmsnorm(x, blk["norm_x"], eps=cfg.norm_eps)
    cross, _ = L.attention(normed, blk["cross"], cfg, positions=positions, causal=False,
                           memory=memory)
    return x + cross


def _mixer(x, blk: Params, cfg: ModelConfig, spec: Spec, positions, causal=True,
           memory=None, state=None, clen=0, n_new=None):
    """The block's first half, residual included: (x_out, new_state)."""
    from ..kernels import ops

    sp = cfg.seq_parallel and x.shape[1] > 1
    normed = ops.rmsnorm(x, blk["norm1"], eps=cfg.norm_eps)
    new_state = None
    if spec.kind == "attn":
        with jax.named_scope("attn.sliding" if spec.window else "attn.full"):
            att, new_state = L.attention(
                normed, blk["mixer"], cfg, positions=positions, causal=causal,
                cache=state, clen=clen, n_new=n_new, window=spec.window, yarn=spec.yarn,
            )
        # Megatron SP: sub-block outputs reduce-scatter onto the sequence dim
        # (1x ring bytes); the next column-parallel matmul all-gathers.
        x = x + (L.constrain(att, ("pod", "data"), "model", None) if sp else att)
        if memory is not None:  # cross-attention sub-block (enc-dec decoder)
            x = _cross(x, blk, cfg, positions, memory)
    elif spec.kind == "mamba":
        out, new_state = L.mamba(normed, blk["mixer"], cfg, state=state)
        x = x + out
    elif spec.kind == "mlstm":
        out, new_state = L.mlstm(normed, blk["mixer"], cfg, state=state)
        x = x + out
    elif spec.kind == "slstm":
        out, new_state = L.slstm(normed, blk["mixer"], cfg, state=state)
        x = x + out
    return x, new_state


def _ffn(x, blk: Params, cfg: ModelConfig, spec: Spec, valid=None):
    """The block's second half, residual included, on x (..., D): (x_out,
    expert counts (2,) int32, zero without experts).  ``valid`` (the
    tokens of x, flattened) marks the tokens the expert layer routes."""
    from ..kernels import ops

    counts = jnp.zeros((2,), jnp.int32)
    if not cfg.d_ff:
        return x, counts
    normed = ops.rmsnorm(x, blk["norm2"], eps=cfg.norm_eps)
    if spec.moe:
        y, counts = L.moe_ffn(normed.reshape(-1, x.shape[-1]), blk["ffn"], cfg, valid=valid)
        y = y.reshape(x.shape)
    else:
        y = L.dense_ffn(normed, blk["ffn"])
    if cfg.seq_parallel and x.ndim == 3 and x.shape[1] > 1:
        y = L.constrain(y, ("pod", "data"), "model", None)
    return x + y, counts


def _apply_block(x, blk: Params, cfg: ModelConfig, spec: Spec, positions, causal=True,
                 memory=None, state=None, clen=0, n_new=None, valid=None):
    """Returns (x_out, new_state, expert counts)."""
    x, new_state = _mixer(x, blk, cfg, spec, positions, causal, memory, state, clen, n_new)
    x, counts = _ffn(x, blk, cfg, spec, valid)
    return x, new_state, counts


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

    specs = stack_specs(cfg)
    if cfg.family == "audio":
        memory = encode(cfg, params, batch["embeds"])

        @_maybe_remat
        def body(xc, blk):
            return _apply_block(xc, blk, cfg, specs[0], positions, memory=memory)[0], None

        x, _ = jax.lax.scan(body, x, params["decoder"])
    elif cfg.period:
        period_params = params["periods"]

        if cfg.remat == "layer":
            # per-position remat: during the period backward only ONE
            # layer's intermediates are live (vs all 8 with period remat)
            def apply_pos(xc, blk, pos):
                return _apply_block(xc, blk, cfg, specs[pos], positions)[0]

            apply_pos = jax.checkpoint(apply_pos, static_argnums=(2,))

            def body(xc, blks):
                for pos, blk in enumerate(blks):
                    xc = apply_pos(xc, blk, pos)
                return xc, None
        else:
            @_maybe_remat
            def body(xc, blks):
                for pos, blk in enumerate(blks):
                    xc = _apply_block(xc, blk, cfg, specs[pos], positions)[0]
                return xc, None

        x, _ = jax.lax.scan(body, x, tuple(period_params))
    else:
        @_maybe_remat
        def body(xc, blk):
            return _apply_block(xc, blk, cfg, specs[0], positions)[0], None

        x, _ = jax.lax.scan(body, x, params["layers"])
    logits = _logits(cfg, params, x)
    if n_front:
        logits = logits[:, n_front:, :]
    return logits


def encode(cfg: ModelConfig, params: Params, embeds: jax.Array) -> jax.Array:
    """Audio encoder over precomputed frame embeddings (bidirectional)."""
    from ..kernels import ops

    b = embeds.shape[0]
    positions = jnp.arange(embeds.shape[1], dtype=jnp.int32)[None, :].repeat(b, 0)

    spec = Spec("attn", False, None, None)

    def body(xc, blk):
        return _apply_block(xc, blk, cfg, spec, positions, causal=False)[0], None

    x, _ = jax.lax.scan(body, embeds, params["encoder"])
    return ops.rmsnorm(x, params["enc_final_norm"], eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------
def _cache_window(cfg: ModelConfig, sp: Spec, ring: bool) -> int | None:
    """The ring size of a layer's cache, None for a full-length one: with
    ``ring`` every windowed layer's; without, only a declared 'sliding'
    layer's (a uniform ``window`` then keeps a full-length cache)."""
    if ring or "sliding" in cfg.layer_types:
        return sp.window
    return None


def _empty_attn_cache(cfg: ModelConfig, b: int, s_max: int, dt, window: int | None) -> tuple:
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    # a windowed layer only ever attends to the last `window` positions: its
    # cache is then a window-sized ring buffer (the sub-quadratic path)
    eff = min(s_max, window) if window else s_max
    shape = (b, kv, eff, dh)
    return (jnp.zeros(shape, dt), jnp.zeros(shape, dt))


def _empty_state(cfg: ModelConfig, kind: str, b: int, s_max: int, dt, window=None):
    d = cfg.d_model
    if kind == "attn":
        return _empty_attn_cache(cfg, b, s_max, dt, window)
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


def _stacked(n: int, st):
    return jax.tree_util.tree_map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), st)


def init_decode_state(cfg: ModelConfig, b: int, s_max: int, ring: bool = True) -> dict:
    """An empty decode state of batch ``b`` for ``s_max`` positions.  Each
    layer stack leads with its layer axis, then the batch axis: ``periods``
    (one stack per position of the period) or ``layers`` (head-major KV
    caches (layers, B, KV, S, Dh)); which layers keep a window-sized ring is
    ``_cache_window``'s rule.  ``counts`` (2,) int32 are the expert counts
    of the step that made the state."""
    dt = _dtype(cfg)
    n_stack = cfg.n_layers // cfg.period if cfg.period else cfg.n_layers
    stacks = [
        _stacked(n_stack, _empty_state(cfg, sp.kind, b, s_max, dt, _cache_window(cfg, sp, ring)))
        for sp in stack_specs(cfg)
    ]
    state = _with_stacks(cfg, {"len": jnp.zeros((), jnp.int32),
                               "counts": jnp.zeros((2,), jnp.int32)}, stacks)
    if cfg.family == "audio":
        state["memory"] = jnp.zeros((b, cfg.frontend_len, cfg.d_model), dt)
    return state


def decode_step(
    cfg: ModelConfig, params: Params, state: dict, tokens: jax.Array, length=None
) -> tuple[jax.Array, dict]:
    """Decode/prefill step: tokens (B, s) -> logits (B, s, V) + new state.

    s == 1 is a decode step; s > 1 prefills the cache, of which ``length``
    (default s) are real tokens and the rest right padding: the state's
    ``len`` advances by ``length``, padded positions route to no expert and
    stay out of a ring (they land past ``len`` in a full-length cache,
    where decode overwrites them).  The new state's ``counts`` are the
    step's expert counts: the (token, held expert) pairs computed and the
    held experts that received a token, summed over layers.
    """
    x, state = _run_layers(cfg, params, state, tokens, length)
    return _logits(cfg, params, x), state


def prefill(cfg: ModelConfig, params: Params, state: dict, tokens: jax.Array,
            length) -> tuple[jax.Array, dict]:
    """``decode_step`` of a prompt (B, s) right-padded after its ``length``
    real tokens, with the logits of its last real position only (B, V):
    the head is not applied to the other positions."""
    x, state = _run_layers(cfg, params, state, tokens, length)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1, keepdims=False)
    return _logits(cfg, params, last), state


def _run_layers(cfg: ModelConfig, params: Params, state: dict, tokens: jax.Array, length):
    """The layers of a decode or prefill step: (hidden states before the
    final norm (B, s, D), new state)."""
    b, s = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    clen = state["len"]
    n_new = s if length is None else length
    positions = clen + jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    valid = jnp.tile(jnp.arange(s) < n_new, b) if cfg.is_moe else None
    specs = stack_specs(cfg)
    memory = state.get("memory")

    def body(carry, inp):
        xc, counts = carry
        blks, sts = inp  # tuples over the stacks, sliced per layer (period)
        new_sts = []
        for spec, blk, st in zip(specs, blks, sts):
            xc, nst, c = _apply_block(xc, blk, cfg, spec, positions, memory=memory, state=st,
                                      clen=clen, n_new=n_new, valid=valid)
            new_sts.append(nst)
            counts = counts + c
        return (xc, counts), tuple(new_sts)

    (x, counts), new = jax.lax.scan(
        body, (x, jnp.zeros((2,), jnp.int32)),
        (tuple(_param_stacks(cfg, params)), tuple(_state_stacks(cfg, state))))
    return x, dict(_with_stacks(cfg, state, new), len=clen + n_new, counts=counts)


# ---------------------------------------------------------------------------
# slot-batched decode (the continuous-batching serve path)
# ---------------------------------------------------------------------------
def init_slot_states(cfg: ModelConfig, n_slots: int, s_max: int) -> dict:
    """Decode states for ``n_slots`` independent request slots: a batch of
    ``n_slots`` decode state (``ring=False``: full-length caches, but a
    window-sized ring for each declared sliding layer) whose ``len`` is one
    per slot.  The serving engine writes a freshly prefilled request into
    one slot with ``write_slot`` while the others are mid-stream."""
    st = init_decode_state(cfg, n_slots, s_max, ring=False)
    st["len"] = jnp.zeros((n_slots,), jnp.int32)
    return st


def write_slot(states: dict, i: int, state: dict) -> dict:
    """Insert a single-slot (``b=1``) decode state at slot index ``i`` of a
    slot-batched state (a refill: the new request's prefilled cache and
    length replace whatever the finished request left behind).  The slot
    axis is 0 for ``len`` and ``memory`` and 1 in the layer stacks (their
    layer axis leads); the batch's ``counts`` stay."""
    def put(axis):
        def f(s, x):
            x = jnp.squeeze(x, axis) if x.ndim == s.ndim else x
            return s.at[(slice(None),) * axis + (i,)].set(x)
        return f

    return {k: v if k == "counts" else
            jax.tree_util.tree_map(put(0 if k in ("len", "memory") else 1), v, state[k])
            for k, v in states.items()}


def _slot_attention(x, p: Params, cfg: ModelConfig, spec: Spec, cache: tuple, i, clen):
    """Self-attention of one token per slot, x (N, 1, D), over the caches of
    stack index ``i`` in a stack (layers, N, KV, S, Dh), each slot at its
    own length ``clen`` (N,): its key roped at its position and its value
    written into its own row (a ring's slot ``clen % S``) in place.
    Returns (output (N, 1, D), the stack's caches)."""
    from ..kernels import ops

    n = x.shape[0]
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ck, cv = cache
    size = ck.shape[3]
    ring = spec.window is not None and size <= spec.window
    with jax.named_scope("attn.sliding" if spec.window else "attn.full"):
        q, k, v = L.project_qkv(x, p, cfg)
        q = L.rope(q, clen[:, None], cfg.rope_theta, spec.yarn)
        k = L.rope(k, clen[:, None], cfg.rope_theta, spec.yarn)
        wpos = jnp.mod(clen, size) if ring else clen
        for j in range(n):
            at = (i, j, 0, wpos[j], 0)
            ck = jax.lax.dynamic_update_slice(ck, k[j, 0][None, None, :, None].astype(ck.dtype), at)
            cv = jax.lax.dynamic_update_slice(cv, v[j, 0][None, None, :, None].astype(cv.dtype), at)
        off = jnp.minimum(clen, size - 1) if ring else clen
        o = ops.attention(q.reshape(n * h, 1, dh), ck[i].reshape(n * kv, size, dh),
                          cv[i].reshape(n * kv, size, dh), causal=True,
                          q_offset=jnp.repeat(off, kv))
    return o.reshape(n, 1, h * dh) @ p["wo"], (ck, cv)


def decode_slots(
    cfg: ModelConfig, params: Params, states: dict, tokens: jax.Array
) -> tuple[jax.Array, dict]:
    """One decode step for every slot at once.

    ``states`` is a slot-batched state (``init_slot_states``); ``tokens`` is
    ``(N,)`` int32 — the last sampled token per slot.  Returns ``(logits
    (N, V), new states)``, whose ``counts`` are the step's expert counts.
    The step is one batch of N tokens: projections, FFN or expert layer,
    recurrent mixers and head take every slot's token at once, so the slots
    share their weight reads; attention ropes, writes and reads each slot's
    cache at its own length, which is what lets a freshly admitted request
    coexist with half-finished ones without any retrace: the traced shapes
    depend only on ``(N, s_max)``.
    """
    from ..kernels import ops

    specs = stack_specs(cfg)
    clen = states["len"]  # (N,)
    memory = states.get("memory")
    positions = clen[:, None]

    def body(carry, inp):
        x, sts, counts = carry
        blks, i = inp
        sts = list(sts)
        for pos, (spec, blk) in enumerate(zip(specs, blks)):
            if spec.kind == "attn":
                normed = ops.rmsnorm(x, blk["norm1"], eps=cfg.norm_eps)
                att, sts[pos] = _slot_attention(normed, blk["mixer"], cfg, spec, sts[pos], i,
                                                clen)
                x = x + att
                if memory is not None:
                    x = _cross(x, blk, cfg, positions, memory)
            else:  # a recurrent state: the N slots are its batch
                st = jax.tree_util.tree_map(lambda a: a[i], sts[pos])
                x, st = _mixer(x, blk, cfg, spec, positions, state=st)
                sts[pos] = jax.tree_util.tree_map(lambda a, b: a.at[i].set(b), sts[pos], st)
            x, c = _ffn(x, blk, cfg, spec)
            counts = counts + c
        return (x, tuple(sts), counts), None

    stacks = _param_stacks(cfg, params)
    n_stack = jax.tree_util.tree_leaves(stacks[0])[0].shape[0]
    x = embed_tokens(cfg, params, tokens)[:, None]  # (N, 1, D)
    (x, sts, counts), _ = jax.lax.scan(
        body, (x, tuple(_state_stacks(cfg, states)), jnp.zeros((2,), jnp.int32)),
        (tuple(stacks), jnp.arange(n_stack)))
    new = dict(_with_stacks(cfg, states, sts), len=clen + 1, counts=counts)
    return _logits(cfg, params, x[:, 0]), new


def decode_slots_greedy(
    cfg: ModelConfig, params: Params, states: dict, tokens: jax.Array
) -> tuple[jax.Array, dict]:
    """``decode_slots`` with the greedy sample fused on device: returns
    ``((N,) int32 next tokens, new states)``.  Keeping the argmax on device
    means the sampled tokens can feed the *next* dispatched step directly —
    the engine's pipelined dispatch only blocks on them at harvest points."""
    logits, states = decode_slots(cfg, params, states, tokens)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), states
