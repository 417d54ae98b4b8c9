"""itl_p99_ms: 99th percentile (nearest rank) of the gaps between two
consecutive tokens of a request as the host saw them, over every gap whose
later token came in the window (about 2,500 a run, so some 25 beyond it):
the gaps a prefill or slot write stalls.  Host clock."""
from bench.serve_counts import nearest_rank


def read(rec):
    return nearest_rank(rec["itl_ms"], 99)
