"""Where entry-point scripts keep JAX's persistent compilation cache.

Scripts call :func:`configure` once, before they compile anything;
importing ``repro`` never touches the cache. An exported
``JAX_COMPILATION_CACHE_DIR`` is left alone (JAX reads it itself).
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path,
because the path is part of each entry's key and a moving directory
never hits.
"""

from __future__ import annotations

import os
from pathlib import Path


def configure(checkout: Path) -> str:
    """Point the persistent cache at its directory; returns that directory."""
    exported = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if exported:
        return exported
    import jax

    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
