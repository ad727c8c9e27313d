"""JAX's persistent compilation cache, in one place.

Entry points (`chip_smoke.py`, `launch/serve.py`, `examples/*`) call
`enable_compile_cache()` before their first compile; importing this module
changes nothing.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and this leaves it alone.  Otherwise the cache goes to a fixed
directory inside the checkout (`.jax_cache/`, git-ignored): the path is
part of what a later run looks up, so it must not move between runs.

`persistent_cache_off()` keeps single compiles out of the cache:
`AccelModule.place` compiles every program that spans more than one
device inside it.
"""
from __future__ import annotations

import contextlib
import os
import threading
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# held while the cache is switched off, so that two blocks cannot restore
# each other's setting early
_OFF_LOCK = threading.RLock()


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


@contextlib.contextmanager
def persistent_cache_off():
    """Compile inside this block with the persistent cache off: nothing is
    read from it and nothing written to it.

    JAX decides once per process whether the cache is in use and keeps the
    answer, so this switches the option off, clears that answer, and on
    exit restores both.  The switch is process-wide: a compile in another
    thread meanwhile goes uncached too, which costs it time, not
    correctness."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    with _OFF_LOCK:
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
