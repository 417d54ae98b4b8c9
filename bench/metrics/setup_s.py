"""setup_s: seconds from the start of the process to the start of the
window (imports, the pass pipeline, planning, input generation, and the
compile or cache load and warm-up call of every program).  Host clock."""


def read(rec):
    return rec["setup_s"]
