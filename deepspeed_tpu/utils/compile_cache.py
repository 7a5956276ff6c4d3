"""JAX's persistent compilation cache at a place the caller can choose.

``JAX_COMPILATION_CACHE_DIR`` set in the environment wins and nothing else
is configured in code; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored). The directory is part of the cache key, so it is never built
from a temporary name, a process id or the time — a cache that moves never
hits.
"""

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir():
    """The directory the persistent cache uses (nothing is created)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_REPO_ROOT, ".jax_cache")


def enable():
    """Point jax's persistent cache at ``cache_dir()``; returns the path.
    With the environment variable set, jax has already read it and no other
    directory is set here."""
    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def entries():
    """(entries, bytes) of the cache directory now: the executables jax
    keeps there (``*-cache``; their access-time files are not counted).
    (0, 0) if the directory is absent."""
    count = size = 0
    try:
        with os.scandir(cache_dir()) as it:
            for entry in it:
                if entry.name.endswith("-cache"):
                    count += 1
                    size += entry.stat().st_size
    except FileNotFoundError:
        pass
    return count, size


def entry_count():
    """Number of entries currently in the cache directory (0 if absent)."""
    return entries()[0]
