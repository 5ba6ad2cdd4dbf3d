"""Persistent XLA compilation cache (SURVEY.md §6.4).

The reference scheduler is stateless and needs no checkpointing; the one
piece of solver state worth persisting across restarts is the XLA
executable cache (SURVEY.md §6.4 "Solver warm state"). Without it every
process start pays the full compile of the scan pipeline on its first
batch. With the cache on disk a restart deserializes the executables
instead (``chip_smoke.py`` phase C reports both start-ups on the chip).

The cache is placed from OUTSIDE, one way: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself; this module sets no other
directory), else ``<checkout>/.jax_cache``. The directory is part of
the cache key's environment, so it is a fixed path — never a temporary
name, a pid or a timestamp.
"""

from __future__ import annotations

import os

_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_enabled = False


def enable_persistent_cache() -> str:
    """Idempotently turn on JAX's persistent compilation cache and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``. An unwritable directory raises — a
    scheduler that silently recompiles on every start is the failure
    this cache exists to prevent.

    Thresholds are zeroed in both cases so even sub-second kernels
    persist: the solve pipeline is one big executable, but the
    tensorizers jit a handful of small helpers whose compiles otherwise
    still add up at startup.
    """
    global _enabled
    import jax

    if _enabled:
        return jax.config.jax_compilation_cache_dir
    cache_dir = (
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR
    )
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK | os.X_OK):
        raise PermissionError(
            f"compile cache directory {cache_dir!r} is not writable"
        )
    # with the variable set this is the value JAX already read from it
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled = True
    return cache_dir


def key_on_op_names() -> None:
    """Make the persistent cache's key cover op metadata, so that an
    executable built from the same program under OTHER ``op_name``s
    (before a ``jax.named_scope`` was added or renamed; the key leaves
    debug information out by default) is not handed back with its old
    names. ``serve --telemetry`` calls this and nothing else does: the
    names matter only to who reads a device trace, and every other
    process keeps today's key and its warm entries."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
