"""serve_mfu.moe: the served steps' share of the chip's peak FLOP/s in the
traced round, in percent: the model operations of its decode steps and
prefills (attention projections, head, and each layer kind's keys from the
slots' cache lengths; the expert layer's from the engine's pairs counter;
``bench/expert_counts.py``) over the seconds the device was busy in it,
over peak FLOP/s.  None without the engine's counters."""
from bench import expert_counts as ec


def read(rec):
    found, tr = ec.round_spans(rec), rec.get("trace")
    if not found or not tr or tr["busy_s"] <= 0:
        return None
    flops = ec.round_flops(rec["model"], *found)
    return 100.0 * flops / tr["busy_s"] / rec["peak"]["flops_per_s"]
