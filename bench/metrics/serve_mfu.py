"""serve_mfu: the served step's share of the chip's peak FLOP/s in the
traced round, in percent: the model operations of the tokens delivered in
the round (a prompt's with its first token; ``bench/serve_counts.py``)
over the seconds the device was busy in it, over peak FLOP/s."""


def read(rec):
    t, tr = rec.get("traced"), rec.get("trace")
    if not t or not tr or tr["busy_s"] <= 0 or not t["flops"]:
        return None
    return 100.0 * t["flops"] / tr["busy_s"] / rec["peak"]["flops_per_s"]
