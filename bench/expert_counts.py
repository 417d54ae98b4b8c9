"""Work counts of a served decoder with per-layer attention kinds and an
expert layer over the experts held here (``mellum2-12b-a2.5b``), read from
the engine's own spans.

The traced round's steps are the last ``record["traced"]["decode_steps"]``
``serve.decode`` spans the engine recorded (the round ends the run's
serving), and its prefills the ``serve.prefill`` spans that opened after
the first of them.  Each span carries the step's expert counters (``pairs``,
(token, held expert) pairs computed; ``experts``, held experts that
received a token; both summed over layers) and a decode span the cache
length of each slot it ran (``lens``).  A program without those spans or
counters reads as None.

Model operations, a lower bound on what any implementation must do: of a
token at position ``p``, the four attention projections of every layer, the
head, and scores and weighted sums over the keys it sees (``min(p + 1,
window)`` on a sliding layer, ``p + 1`` on a full one); of a step's expert
layer, ``pairs`` times three products of ``d_model`` by ``d_ff`` (two
operations per multiply-add).
"""
from __future__ import annotations

from bench.program_spans import spans


def round_spans(rec: dict) -> tuple[list, list] | None:
    """The traced round's decode and prefill spans, or None."""
    t = rec.get("traced")
    n = t and t.get("decode_steps")
    decode = [s for s in spans("serve.decode") if "pairs" in s.attrs]
    if not n or len(decode) < n:
        return None
    decode = decode[-n:]
    start = decode[0].start_ns
    prefill = [s for s in spans("serve.prefill") if s.start_ns >= start and "pairs" in s.attrs]
    return decode, prefill


def expert_flops(m: dict, pairs: int) -> int:
    return pairs * 3 * 2 * m["d_model"] * m["d_ff"]


def kinds(m: dict) -> list[str]:
    """Each layer's attention kind."""
    period = m["layer_types"]
    return [period[layer % len(period)] for layer in range(m["n_layers"])]


def token_flops(m: dict, pos: int) -> int:
    """Operations of one token at position ``pos`` outside the expert layer."""
    d, dh = m["d_model"], m["d_head"]
    proj = d * m["n_heads"] * dh * 2 + d * m["n_kv_heads"] * dh * 2
    keys = sum(pos + 1 if k == "full" else min(pos + 1, m["window"]) for k in kinds(m))
    return 2 * (m["n_layers"] * proj + d * m["vocab"]) + 4 * m["n_heads"] * dh * keys


def round_flops(m: dict, decode: list, prefill: list) -> int:
    """Model operations of the round's decode steps and prefills."""
    total = 0
    for s in decode:
        total += sum(token_flops(m, n) for n in s.attrs["lens"])
        total += expert_flops(m, s.attrs["pairs"])
    for s in prefill:
        total += sum(token_flops(m, p) for p in range(s.attrs["tokens"]))
        total += expert_flops(m, s.attrs["pairs"])
    return total
