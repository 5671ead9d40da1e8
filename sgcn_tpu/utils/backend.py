"""Backend selection and compile-cache placement shared by the entry points.

``-b cpu`` is the reference's Gloo "cluster on one box" mode
(``GPU/PGCN.py:166-169``): k virtual host CPU devices standing in for k
chips.  The XLA flag must be in the environment before XLA initializes its
backend — package imports may already have imported ``jax`` (module import
is fine; backend init is lazy), so the platform choice itself goes through
``jax.config.update``, which works post-import.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — derived from the package location, git-ignored.
# The directory is part of the cache key, so it must never move between
# runs (no temporary name, pid or timestamp).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_cpu_devices(nparts: int) -> None:
    """Force ``nparts`` virtual host CPU devices for this process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={nparts}"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that survives the
    process; returns the directory in use.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and this sets no other; otherwise the cache
    lives at ``COMPILE_CACHE_DIR``.

    Either way op metadata is made part of the cache key.  By default JAX
    strips it, so a program that differs from a cached one only in its
    ``jax.named_scope``s (``obs.tracing.scope``) or source lines is handed
    the cached executable WITH THE OLD NAMES, and a profile of it reads by
    scopes the running program does not have (or lacks the ones it has:
    measured on the chip, PR 25 — the parent commit's trace showed the
    change's scopes through a shared cache directory)."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
