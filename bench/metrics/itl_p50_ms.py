"""itl_p50_ms: median (nearest rank) of the gaps between two consecutive
tokens of a request as the host saw them, over every gap whose later token
came in the window: the pace of the decode step.  Host clock."""
from bench.serve_counts import nearest_rank


def read(rec):
    return nearest_rank(rec["itl_ms"], 50)
