"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the benchmark CLIs) call
:func:`use_compile_cache` before they compile anything; importing the
library sets no cache.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache's place when ``JAX_COMPILATION_CACHE_DIR`` names none: a fixed
#: directory inside the checkout (git ignores it).  The path is part of the
#: cache's key, so a directory that moved between runs would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
