"""Persistent XLA compilation cache: wiring, stats, and AOT hit accounting.

Every fresh process pays the full XLA compile bill at step 1 unless the
compiled executable can be fetched from somewhere — jax's persistent
compilation cache is that somewhere: a content-addressed directory of
serialized executables, safe for concurrent writers (each entry is written
once under a hash key), which makes it exactly right for a shared
filesystem on a multi-host pod: every host points at the same directory and
the first job to compile pays for everyone.

``configure_cache`` is the one entry point. ``TrainingPipeline`` and
``ServeEngine`` call it before their first compile (the cache is ON by
default for both); scripts that compile before building either call it at
program start. There is ONE rule for the directory:

1. if ``$JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
   and this module sets no other (an explicit path argument is ignored with
   one log line) — a launcher that sets the variable decides where compiled
   programs survive;
2. otherwise an explicit path argument, if one was given;
3. otherwise ``<checkout>/.jax_cache`` — a FIXED path next to the package
   (the path is part of jax's cache key, so a directory that moves never
   hits), never a home, temporary, pid- or time-derived one.

``jax_enable_compilation_cache=False`` (jax's own switch; the test session
sets it, tests/conftest.py) turns the whole thing off.

Stats are two-layered: ``cache_stats()`` reports the on-disk population
(entries/bytes — shared across every process using the dir) plus this
process's AOT-phase counters (hits = programs the precompiler loaded from
the cache, misses = programs it had to compile). On shared filesystems only
process 0 should log them (``TrainingPipeline`` does).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any

import jax
from jax.experimental.compilation_cache import compilation_cache as _jax_cache

__all__ = [
    "ENV_VAR",
    "configure_cache",
    "default_cache_dir",
    "resolve_cache_dir",
    "configured_cache_dir",
    "entry_count",
    "record_compile",
    "cache_stats",
    "reset_process_stats",
]

#: jax's own variable: jax reads it into ``jax_compilation_cache_dir`` at import
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_logger = logging.getLogger("dmlcloud_tpu")
_lock = threading.Lock()
_aot_hits = 0
_aot_misses = 0
_aot_compile_ms = 0.0


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the directory that holds the package."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def configured_cache_dir() -> str | None:
    """The directory jax's persistent cache currently writes to, or None
    (no directory set, or jax's cache switched off)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None


def resolve_cache_dir(cache_dir: Any = True) -> str | None:
    """Resolve the cache directory per the module docstring's rule without
    touching jax config. ``None``/``False`` disables (returns None)."""
    if cache_dir in (None, False):
        return None
    explicit = os.fspath(cache_dir) if isinstance(cache_dir, (str, os.PathLike)) else None
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        if explicit is not None and os.path.abspath(explicit) != os.path.abspath(from_env):
            _logger.info(
                "compile cache: ignoring explicit directory %s, $%s=%s decides", explicit, ENV_VAR, from_env
            )
        return os.path.abspath(from_env)
    return os.path.abspath(explicit) if explicit is not None else default_cache_dir()


def configure_cache(cache_dir: Any = True) -> str | None:
    """Point jax's persistent compilation cache at the resolved directory
    (see above), creating it. Must run before the first compilation of the
    programs it should cover. Returns the directory, or None when disabled
    (``cache_dir`` None/False, or ``jax_enable_compilation_cache`` off).

    Also drops jax's minimum-compile-time / minimum-entry-size thresholds so
    every program is persisted — the right trade for training and serving
    jobs, where a cache entry costs kilobytes and a cold recompile costs
    seconds to minutes."""
    resolved = resolve_cache_dir(cache_dir)
    if resolved is None or not jax.config.jax_enable_compilation_cache:
        return None
    os.makedirs(resolved, exist_ok=True)
    previous = jax.config.jax_compilation_cache_dir
    if previous != resolved:
        _jax_cache.set_cache_dir(resolved)
        if previous:
            # jax opens the directory it finds at the first compilation and
            # keeps it; moving to another one means dropping that handle
            _jax_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return resolved


def _entry_files(directory: str) -> list[str]:
    # jax writes `<key>-cache` payloads (some versions add `<key>-atime`
    # bookkeeping files and tmp files mid-write; neither is an entry)
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    return [
        os.path.join(directory, n)
        for n in names
        if not n.endswith("-atime") and not n.endswith(".tmp") and not n.startswith(".")
    ]


def entry_count(directory: str | None = None) -> int | None:
    """Number of persisted executables in the cache dir (None if disabled)."""
    directory = directory or configured_cache_dir()
    if directory is None:
        return None
    return len(_entry_files(directory))


def record_compile(hit: bool, elapsed_ms: float) -> None:
    """Account one AOT-phase compilation for this process's stats."""
    global _aot_hits, _aot_misses, _aot_compile_ms
    with _lock:
        if hit:
            _aot_hits += 1
        else:
            _aot_misses += 1
        _aot_compile_ms += float(elapsed_ms)


def reset_process_stats() -> None:
    global _aot_hits, _aot_misses, _aot_compile_ms
    with _lock:
        _aot_hits = _aot_misses = 0
        _aot_compile_ms = 0.0


def cache_stats() -> dict:
    """On-disk population + this process's AOT counters, JSON-encodable.

    When the cache is not enabled yet, ``dir`` still reports what
    ``configure_cache()`` *would* use so ``diag`` shows an actionable path
    either way."""
    enabled_dir = configured_cache_dir()
    directory = enabled_dir or resolve_cache_dir(True)
    entries = size = 0
    if enabled_dir and os.path.isdir(enabled_dir):
        files = _entry_files(enabled_dir)
        entries = len(files)
        for f in files:
            try:
                size += os.path.getsize(f)
            except OSError:
                pass
    with _lock:
        hits, misses, ms = _aot_hits, _aot_misses, _aot_compile_ms
    return {
        "enabled": enabled_dir is not None,
        "dir": directory,
        "entries": entries,
        "size_bytes": size,
        "aot_hits": hits,
        "aot_misses": misses,
        "aot_compile_ms": round(ms, 3),
    }
