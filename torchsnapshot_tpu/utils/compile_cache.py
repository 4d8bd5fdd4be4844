"""Where PROGRAMS keep XLA's persistent compile cache.

Called by the entry points that touch the chip (``chip_smoke.py``,
``benchmarks/link_probe.py``, ``__graft_entry__.py``) before their
first compile.  Importing the library never calls it: a library does
not set process-wide JAX configuration.

A later process finds the entries only in the SAME directory, so it is
either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that
variable itself; nothing is set in code then) or a fixed path beside the
package — never a temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    cache_dir = os.environ.get(_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # keep the many sub-second programs too (one unpack program per leaf
    # signature, the AOT tile updates): by default only compiles that
    # took over a second are written
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
