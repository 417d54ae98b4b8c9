"""daisy_compile_s: seconds in ``Daisy.compile`` over the run (its pass
pipeline, planning and code-generator set-up, memo hits included), summed
from the program's ``daisy.compile`` spans.  Program span; traced run only."""
from bench.program_spans import seconds


def read(rec):
    return seconds("daisy.compile")
