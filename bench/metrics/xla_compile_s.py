"""xla_compile_s: seconds in XLA's backend compile of Daisy's modules, or in
loading them from the persistent compile cache, summed from the program's
``xla.compile`` spans (recorded from JAX's own compile events).  Program
span; traced run only."""
from bench.program_spans import seconds


def read(rec):
    return seconds("xla.compile")
