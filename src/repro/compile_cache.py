"""JAX's persistent compilation cache, placed for entry-point scripts.

Scripts (``chip_smoke.py``, the benchmarks, the examples) call
``enable()`` before their first compile; library code never does, so
importing ``repro`` leaves JAX's configuration alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path (never a temp name, pid or time), so
a second run from the same checkout finds what the first compiled.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
