"""Where this checkout keeps jax's persistent compilation cache.

``chip_smoke.py`` and ``tools/pallas_bench.py`` call
:func:`enable_compile_cache` before their first compile (the benchmark
keeps the same rule in ``harness.enable_compile_cache``), so the runs of one
command share their compilations: a cold ResNet-50 step alone is most of
a minute of compile on a v5e chip.
"""

import os

# The directory is part of the cache key: a path that moves (a temporary
# name, a pid, a timestamp) never hits. One fixed, git-ignored directory
# at the root of the checkout.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it and nothing
    is set in code; otherwise the cache goes to :data:`CACHE_DIR`. Call
    before the first compile — jax opens the cache once."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
