"""Where JAX's persistent compilation cache lives for this checkout.

A chip run starts from a cold machine, and the hot paths take tens of
seconds to compile; the cache directory is part of the cache key, so it
must not move between runs. ``chip_smoke.py``, ``bench.py`` and
``bench_serve.py`` call :func:`configure_compile_cache` before their first
compile.
"""
from __future__ import annotations

import os

#: fixed, git-ignored, inside the checkout — never a temp name, pid or time
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Returns the cache directory in force. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
    nothing is set in code; otherwise ``jax_compilation_cache_dir`` becomes
    :data:`CACHE_DIR`."""
    import jax

    # the step programs name their parts in HLO metadata
    # (observability.step_scope) and a device trace reads the names out of
    # the executable: by default the key leaves metadata out, and a program
    # would then be served an executable compiled before it had its names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
