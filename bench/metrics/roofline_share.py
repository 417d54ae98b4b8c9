"""roofline_share: the generated code's share of its roofline, in percent:
the sum over programs of calls times the least time a call can take on the
chip (the larger of its operations over peak FLOP/s and its compulsory
bytes over peak bytes/s, counted from the source program by
``bench/counts.py``), over the sum of the programs' measured times."""
from bench.counts import least_seconds


def read(rec):
    least = sum(p["calls"] * least_seconds(p["flops"], p["bytes"], rec["peak"])[0]
                for p in rec["programs"])
    spent = sum(p["seconds"] for p in rec["programs"])
    return 100.0 * least / spent
