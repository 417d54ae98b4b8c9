"""Isolation of JAX's process-wide matmul precision between tests.

A benchmark run sets ``jax_default_matmul_precision`` from its
configuration (``bench.harness.use_precision``).  An xdist worker runs many
test files in one process, so without this the setting would carry into
unrelated tests, such as the TPU compiles of bfloat16 kernels, which a
float32 contraction precision makes the Mosaic compiler refuse."""
import jax
import pytest


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)
