"""JAX persistent compilation cache: one init function, one placement
rule.

:func:`initialize_compile_cache` is called by the entry points that
run programs (``chip_smoke.py``, ``bench.py``, the ``ceph_tpu/bench``
CLIs) and never on import.  The cache directory is:

- ``JAX_COMPILATION_CACHE_DIR`` when it is set — jax reads that
  variable itself, so no other directory is set in code;
- otherwise the fixed path ``<checkout>/.jax_cache/`` (git-ignored).
  The path is part of every entry's key, so it is never built from a
  temp name, a PID or the time: a directory that moves never hits.

Wiring notes, re-checked on jax 0.9 (tests/test_serve.py's
two-process test pins them):

- The thresholds ``jax_persistent_cache_min_compile_time_secs`` and
  ``min_entry_size_bytes`` are zeroed: the default 1-second floor
  would silently skip every small EC program and the cache would look
  wired while caching nothing.
- A persistent-cache HIT still emits jax's ``backend_compile``
  duration event (checked on 0.9), so "second process compiled
  nothing" is asserted on the cache-miss counter, not on the compile
  count:
  ``install_cache_monitor`` folds jax's
  ``/jax/compilation_cache/cache_hits|cache_misses`` events into the
  telemetry registry as ``jax_persistent_cache_hits`` /
  ``jax_persistent_cache_misses`` — a warm replay is
  ``misses == 0 and hits > 0``.
"""

from __future__ import annotations

import os

from .locks import make_lock

# NOTE: telemetry is imported lazily inside the functions below — the
# telemetry modules create their registry locks through utils.locks,
# so a module-scope import here would close an import cycle
# (telemetry.* → utils → compile_cache → telemetry).

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = make_lock("utils.compile_cache._lock")
_monitor_installed = False


def compile_cache_dir() -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def initialize_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at
    :func:`compile_cache_dir` with zeroed write thresholds, and fold
    its hit/miss events into telemetry.  Idempotent; returns the
    directory."""
    import jax

    d = compile_cache_dir()
    if d == DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", d)
    # zero the write thresholds: EC programs compile in well under
    # the default 1 s floor and would never be cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_cache_monitor()
    return d


def install_cache_monitor() -> bool:
    """Fold jax's persistent-cache hit/miss monitoring events into the
    telemetry registry (``jax_persistent_cache_hits`` /
    ``jax_persistent_cache_misses``).  Idempotent."""
    global _monitor_installed
    with _lock:
        if _monitor_installed:
            return True
        import jax.monitoring

        def _listener(name: str, **kw) -> None:
            from ..telemetry import metrics as tel
            if name == "/jax/compilation_cache/cache_hits":
                tel.counter("jax_persistent_cache_hits")
            elif name == "/jax/compilation_cache/cache_misses":
                tel.counter("jax_persistent_cache_misses")

        jax.monitoring.register_event_listener(_listener)
        _monitor_installed = True
        return True


def cache_entries(cache_dir: str | None = None) -> int:
    """Number of cached executables on disk (``*-cache`` files)."""
    d = cache_dir or compile_cache_dir()
    if not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))


__all__ = ["DEFAULT_DIR", "ENV_VAR", "cache_entries", "compile_cache_dir",
           "initialize_compile_cache", "install_cache_monitor"]
