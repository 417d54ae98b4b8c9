"""mfu: the window's share of the chip's peak FLOP/s, in percent: the
operations the source programs require (``bench/counts.py``) times their
calls, over the window's wall time, over peak FLOP/s."""


def read(rec):
    flops = sum(p["flops"] * p["calls"] for p in rec["programs"])
    return 100.0 * flops / rec["window_s"] / rec["peak"]["flops_per_s"]
