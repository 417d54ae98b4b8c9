"""idle_share.serve: share of the serving cell's traced round in which no
operation ran on the device, in percent (1 - union of device op intervals
/ round, from the profiler trace).  Traced run only."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
