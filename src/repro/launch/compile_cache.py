"""JAX's persistent compilation cache, placed once per process.

Every chip run starts a fresh process, and compiling the kernels and
jitted steps is a large part of a short run; the persistent cache lets
one run reuse what an earlier one compiled.  ``JAX_COMPILATION_CACHE_DIR``
names the directory when it is set (JAX reads the variable itself, and
nothing here overrides it); otherwise the cache lives at a fixed path
inside the checkout, ``.jax_cache/`` (git-ignored).  The path is part of
what makes a later run find its entries, so it never depends on a
temporary directory, a process id or the time.

The one setting changed in both cases is the minimum compile time for
an entry to be written, set to 0: a kernel or a small jitted step
compiles in well under JAX's default one second and would never be
cached.

Call :func:`setup_compile_cache` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if not (CHECKOUT / "src" / "repro").is_dir():
            raise RuntimeError(
                f"repro is not imported from a checkout ({CHECKOUT} has no "
                f"src/repro); set JAX_COMPILATION_CACHE_DIR")
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
