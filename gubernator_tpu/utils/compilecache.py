"""Persistent XLA compilation cache: one rule for where it lives.

A daemon start compiles the decide/inject/census/admission programs and
the width ladder before it serves; JAX's content-addressed on-disk
executable cache turns every one of those into a deserialize on the
next start. The reference has no analog (Go rate-limit arithmetic
doesn't compile), but its operational bar — a daemon is serving within
seconds of exec (reference daemon.go setup path) — is the contract this
restores.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already has the
directory and this module sets none. Otherwise the directory is
``<checkout>/.jax_cache``, resolved from this package's own path — a
fixed place, because the directory is part of the cache key and a cache
that moves never hits. A process the caller pinned to CPU stays
uncached unless the variable is set: XLA:CPU AOT reload compares
machine-feature lists and can refuse across heterogeneous hosts, and
CPU compiles are seconds.

Called from every entry point that touches a device: the daemon
(cmd/daemon.py), the cluster runner and the graft entry.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger("gubernator.compilecache")

_enabled = False

DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def cache_dir() -> str:
    """The directory in force, whether or not the cache is enabled yet."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on under the module's one
    rule. Idempotent; returns the cache dir, or None when the process
    runs uncached (CPU-pinned with no directory given, or the default
    directory cannot be created)."""
    global _enabled
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if (jax.config.jax_platforms or "").lower() == "cpu":
            return None
        try:
            os.makedirs(DEFAULT_DIR, exist_ok=True)
        except OSError as e:  # unwritable checkout: run uncached rather than die
            log.warning(
                "compile cache dir %s unavailable: %s", DEFAULT_DIR, e
            )
            return None
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Cache every compile that takes >=1s (the default threshold would
    # skip most of our kernels).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    _enabled = True
    return jax.config.jax_compilation_cache_dir


def cache_stats() -> dict:
    """Compile-cache observability for /debug/device: whether the
    persistent cache is live, its on-disk footprint (entry count +
    bytes), and the process-wide compile counters (hits/compiles/
    seconds) from the runtime telemetry listener. Disk census is a
    single scandir — cheap enough for a debug route, not run per
    scrape."""
    path = cache_dir() if _enabled else None
    entries = 0
    disk_bytes = 0
    if path:
        try:
            with os.scandir(path) as it:
                for e in it:
                    if e.is_file(follow_symlinks=False):
                        entries += 1
                        disk_bytes += e.stat(follow_symlinks=False).st_size
        except OSError:
            pass
    # Lazy: runtime package pulls jax; this module must import without.
    from gubernator_tpu.runtime import telemetry

    out = {
        "enabled": _enabled,
        "path": path,
        "entries": entries,
        "disk_bytes": disk_bytes,
    }
    out.update(telemetry.compile_counters())
    return out
