"""decode_roofline: the decode step's share of its roofline in the traced
round, in percent: the mean least time of the decode steps dispatched in it
(their weight bytes plus the live cache bytes of their occupied slots over
peak bytes/s, or their operations over peak FLOP/s if larger;
``bench/serve_counts.py``) over the mean device time of one run of the
decode module in the trace.  None when the trace holds no such run."""


def read(rec):
    t = rec.get("traced")
    if not t or not t["decode_module"] or not t["decode_least_s_mean"]:
        return None
    _, seconds, runs = t["decode_module"]
    return 100.0 * t["decode_least_s_mean"] * runs / seconds
