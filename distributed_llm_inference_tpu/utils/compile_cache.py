"""Where the persistent XLA compilation cache lives.

One rule for every launcher (serving/server.py, serving/stage_runtime.py):
where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
the program sets no directory in code — the operator placed the cache.
Where it is not, the cache is `<checkout>/.xla_cache`, computed from this
package's location: the directory is part of the cache key, so a temp name,
a pid or a timestamp in the path would never hit. Spawned replicas and
stages run from the same checkout (or inherit the variable), so they share
the one directory.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    """The directory compiled programs persist in (see module docstring)."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".xla_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile. Even fast-to-compile programs are
    cached: restart latency is the point."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()
