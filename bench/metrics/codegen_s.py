"""codegen_s: seconds the code generator spent emitting the programs' JAX
code (it runs while JAX traces each program once), summed from the
program's ``codegen.emit`` spans.  Program span; traced run only."""
from bench.program_spans import seconds


def read(rec):
    return seconds("codegen.emit")
