"""JAX's persistent compilation cache for entry points that compile for a chip.

The library never turns the cache on by itself (tests and embedding
programs keep their own settings); entry points call ``enable`` once, before
their first compile.  A later run finds an entry only where an earlier one
left it, so the directory never moves between runs:
``JAX_COMPILATION_CACHE_DIR`` when it is set, else one fixed directory the
caller names (``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import os
from pathlib import Path


def enable(default_dir: Path) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile is cached, however quick: the stream kernels compile in
    about a second, under JAX's default one-second threshold, and a cold
    run compiles one program per batch width the server launches."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(default_dir)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
