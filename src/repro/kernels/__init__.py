"""Pallas TPU kernels for the perf-critical compute of the model stack.

Each kernel module pairs with an oracle in ``ref.py``; ``ops.py`` exposes the
backend-switching public API (xla / pallas_interpret / pallas).
"""
from . import ops, ref  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .gemm import gemm  # noqa: F401
from .rmsnorm import rmsnorm  # noqa: F401
