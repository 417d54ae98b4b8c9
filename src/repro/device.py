"""The platform this process runs on, and what it implies.

* ``pallas_backend`` — Pallas kernels compile on a TPU and run in the
  interpreter everywhere else; ``Daisy`` and ``Schedule`` take it when no
  backend is given.
* ``use_compile_cache`` — where JAX keeps its persistent compile cache.
  Entry points (scripts, CLIs, examples) call it; importing a library
  module never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, since the path is part of the key
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_backend() -> str:
    """``'pallas'`` (compiled) on a TPU, ``'pallas_interpret'`` elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache in ``JAX_COMPILATION_CACHE_DIR``
    when that is set (JAX reads it itself), else in ``<checkout>/.jax_cache``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
