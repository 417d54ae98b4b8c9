"""run_ms_geomean: geometric mean, over the cell's programs, of each
program's window time per call (wall time of its slices over its calls), in
milliseconds.  Host clock; every call of the window counts."""
import math


def read(rec):
    per = [p["seconds"] / p["calls"] * 1e3 for p in rec["programs"]]
    return math.exp(sum(math.log(x) for x in per) / len(per))
