"""JAX's persistent compilation cache, placed for the entry points.

Called by the command-line entry points only (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/fleet_sim.py``), never on library import
or from tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives at
``<checkout>/.cache/jax``: a fixed path, because the directory is part of
what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
