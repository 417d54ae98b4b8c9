"""lower_s: seconds JAX spent lowering Daisy's modules (``jit_daisy_*``)
from jaxpr to MLIR, summed from the program's ``jax.lower`` spans, which it
records from JAX's own compile events.  Program span; traced run only."""
from bench.program_spans import seconds


def read(rec):
    return seconds("jax.lower")
