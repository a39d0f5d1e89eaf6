"""Where JAX's persistent compile cache lives, for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache goes to one fixed directory
inside the checkout (``<repo>/.jax_cache``, git-ignored): the path is part
of what a later process must find again, so it is never a temporary, pid-
or time-based name.  A process pinned to the host (``JAX_PLATFORMS=cpu``:
tests, rehearsals) keeps no cache, since XLA:CPU executables read back
from it log machine-feature mismatch errors.

Either way, source paths are made relative to the checkout before they
enter a program's location metadata.  A Pallas TPU kernel carries its
Mosaic module, source locations included, inside the custom call's
backend config, which the cache key hashes whole; with absolute paths the
same step compiled in another checkout would never hit the cache.
"""
from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Optional

REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"
SOURCE_PREFIX_REGEX = "^" + re.escape(f"{REPO_ROOT}{os.sep}")


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compile cache; returns its directory, or
    None for a host-only process."""
    import jax
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          SOURCE_PREFIX_REGEX)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
