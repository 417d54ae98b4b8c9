"""queue_ms_p50: median (nearest rank) over the requests due in the window
of the wait from when each was due to the start of the ``step()`` that
admitted it (its prefill, and those admitted before it in that step, are
not queueing).  Host clock; traced run only."""
from bench.serve_counts import nearest_rank


def read(rec):
    return nearest_rank(rec["queue_ms"], 50)
