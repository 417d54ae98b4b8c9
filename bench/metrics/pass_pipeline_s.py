"""pass_pipeline_s: seconds of one uncached run of the compiler's pass
pipeline (``Daisy.explain``: normalization, rewrites, fusion) over every
program of the cell, summed from the ``PassContext`` records.  Host clock;
traced run only."""


def read(rec):
    vals = [p.get("pass_s") for p in rec["programs"]]
    if any(v is None for v in vals):
        return None
    return sum(vals)
