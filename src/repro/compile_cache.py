"""JAX's persistent compilation cache, set up once for every entry point.

``chip_smoke.py``, ``benchmarks/run.py`` and ``tests/conftest.py`` call
:func:`enable_compile_cache` before they compile anything.  The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``).  The
path is part of each entry's key, so it never depends on a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache``: this file lives at ``<checkout>/src/repro/``.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at the chosen directory; return it.

    The threshold is 0 s, so even sub-second programs (the test suite's
    small scans) are kept.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
