"""The package's seams to jax's own configuration: the `shard_map` entry
point of the installed jax, and where the persistent compilation cache
lives."""
from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def shard_map(fn, *, check_vma: bool = True, **kw):
    """`jax.shard_map` (replication checking is `check_vma`)."""
    return jax.shard_map(fn, check_vma=check_vma, **kw)


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its one directory and
    return the path.  Entry points call this first; nothing calls it at
    import.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is that directory (jax reads
    the variable itself; it is applied again here in case jax was
    imported before the variable was set) and no other is set.
    Otherwise the cache is ``<repo>/.jax_cache``: a fixed path, because
    the directory is part of what lets a cold run find a compile again —
    a temp, pid or time-based path would never hit.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
