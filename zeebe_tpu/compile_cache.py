"""Where a checkout keeps what it caches between runs.

One rule, used by the broker launcher, ``zbench`` and ``chip_smoke.py``:
if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its persistent
compile cache there and no directory is set in code; otherwise the cache
goes to ``<checkout>/.jax_cache``. The path is part of JAX's cache key, so
it carries nothing that moves (no machine fingerprint, backend, pid or
time). The boot autotune's decision table lives in the same directory.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_root() -> str:
    """The cache directory in force (not created here)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable() -> str:
    """Turn JAX's persistent compile cache on under the rule above; call
    before the first compile. Returns the directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_root())
    return cache_root()
