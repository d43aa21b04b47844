"""Where JAX's persistent compilation cache lives.

Every call to the chip starts on a fresh machine, so a run that does not
share a cache recompiles everything.  The cache's path is part of its key:
it must be the same in every process of a checkout, so it is either what
the environment says or ONE fixed directory inside the checkout — never a
temporary name, a process id or a time.

Called by the entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) before their first compile, and by nothing at
package import: tests and library users keep JAX's own default.
"""

from __future__ import annotations

import os

#: <repo>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
