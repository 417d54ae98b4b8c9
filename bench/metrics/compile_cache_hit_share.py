"""compile_cache_hit_share: share of Daisy's module compiles that the
persistent compile cache served, in percent: hits over hits and misses,
counted on the program's ``xla.compile`` spans (attribute ``cache``).
Program counter; traced run only."""
from bench.program_spans import spans


def read(rec):
    outcomes = [s.attrs.get("cache") for s in spans("xla.compile")]
    hits, misses = outcomes.count("hit"), outcomes.count("miss")
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
