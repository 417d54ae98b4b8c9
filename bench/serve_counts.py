"""Work counts of a served dense decoder, and the order statistic the
serving metrics report.

Counts come from the configuration's widths alone (``model`` of
``bench/configs/<config>.json``), never from the program, and are lower
bounds on what any implementation must do, so a share of the chip's peak
built from them stays at or below 100%:

* operations: two per multiply-add of every weight matrix a token passes
  through (the four attention projections, the three SwiGLU matrices of
  every layer, and the output head; the embedding is a lookup), and two
  per multiply-add of attention's scores and weighted sum over the keys
  the token sees;
* bytes of one decode step: every weight read once (the embedding's rows
  of the step's tokens only) and the cached keys and values of every live
  token of the occupied slots read once.
"""
from __future__ import annotations

import math

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def matmul_params(m: dict) -> int:
    """Weights a token multiplies by: attention, SwiGLU and the head."""
    d, dh = m["d_model"], m["d_head"]
    attn = d * m["n_heads"] * dh * 2 + d * m["n_kv_heads"] * dh * 2
    ffn = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + ffn) + d * m["vocab"]


def param_bytes_per_step(m: dict, n_tokens: int) -> int:
    """Weight bytes one decode step of ``n_tokens`` tokens reads: every
    matrix and norm, and the embedding's rows of the step's tokens."""
    norms = (2 * m["n_layers"] + 1) * m["d_model"]
    rows = n_tokens * m["d_model"]
    return (matmul_params(m) + norms + rows) * ITEMSIZE[m["dtype"]]


def kv_bytes_per_token(m: dict) -> int:
    """Bytes of one token's keys and values over every layer."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] * ITEMSIZE[m["dtype"]]


def seen(m: dict, pos: int) -> int:
    """Keys a token at position ``pos`` attends to (itself included)."""
    w = m.get("window")
    return pos + 1 if w is None else min(pos + 1, w)


def attention_flops(m: dict, pos: int) -> int:
    """Scores and weighted sum of one token at position ``pos``."""
    return 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * seen(m, pos)


def token_flops(m: dict, pos: int) -> int:
    """Operations of one token at position ``pos``."""
    return 2 * matmul_params(m) + attention_flops(m, pos)


def prefill_flops(m: dict, length: int) -> int:
    """Operations of a prompt of ``length`` tokens (causal: token ``p`` sees
    ``p + 1`` keys, or the window)."""
    w = m.get("window")
    if w is None or length <= w:
        keys = length * (length + 1) // 2
    else:
        keys = w * (w + 1) // 2 + (length - w) * w
    return (2 * matmul_params(m) * length
            + 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * keys)


def decode_least_seconds(m: dict, live: list[int], peak: dict) -> float:
    """Least time of one decode step whose occupied slots hold ``live``
    tokens each: its weight and live cache bytes over peak bytes/s, or its
    operations over peak FLOP/s, whichever is larger."""
    nbytes = (param_bytes_per_step(m, len(live))
              + kv_bytes_per_token(m) * sum(seen(m, n) for n in live))
    flops = sum(token_flops(m, n) for n in live)
    return max(nbytes / peak["bytes_per_s"], flops / peak["flops_per_s"])


def nearest_rank(values: list[float], q: float) -> float | None:
    """The ``q``-th percentile by nearest rank (the smallest value with at
    least ``q`` percent of the sample at or below it); None when empty."""
    if not values:
        return None
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
