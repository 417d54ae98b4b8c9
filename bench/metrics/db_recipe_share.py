"""db_recipe_share: share of the cell's canonical nests whose recipe came
from the transfer-tuning database (an exact fingerprint or a transfer from
a near one) rather than an idiom default, in percent.  Counted from the
plans."""


def read(rec):
    sources = [s for p in rec["programs"] for s in p["sources"]]
    if not sources:
        return None
    from_db = sum(s == "exact" or s.startswith("transfer") for s in sources)
    return 100.0 * from_db / len(sources)
