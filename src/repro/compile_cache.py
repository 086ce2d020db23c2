"""JAX's persistent compilation cache for the repository's entry points.

``enable_compile_cache`` is called by ``chip_smoke.py`` and
``benchmarks/simbench.py`` at the start of ``main``, never at import, so
tests and library users keep JAX's own defaults.
"""

from __future__ import annotations

import os
import pathlib

# the checkout root: src/repro/compile_cache.py -> src/repro -> src -> root
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and nothing is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, so a second run
    from the same checkout finds the first run's programs — and every
    program is cached, however fast it compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
