"""JAX's persistent compilation cache for the launchers and the chip smoke.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module does nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
checkout root: a fixed path, so that the next run of this checkout finds
what this one wrote.
"""
import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
