"""ceph_tpu.utils — observability & debug surfaces.

- ``perf``  — perf-counter registry (src/common/perf_counters.{h,cc}
  role) + jax.profiler trace hook (the LTTng/`ceph daemon X perf dump`
  analog, SURVEY.md §5 tracing row).
- ``debug`` — sanitizer-equivalent switches (SURVEY.md §5 race/
  sanitizer row): jax debug_nans/checkify-style verification mode for
  the compute paths.
- ``config`` — typed option schema (options.cc role) + the
  erasure-code-profile store (`ceph osd erasure-code-profile`,
  OSDMonitor validation-by-instantiation).
- ``log`` — dout-style per-subsystem leveled debug logging.
- ``errors`` — the structured error catalogue (TransientBackendError /
  RetryExhausted / ScrubError / UnrecoverableError) shared by chaos/,
  scrub/, retry and the backend fallback policy (docs/ROBUSTNESS.md).
- ``retry`` — bounded retry/backoff with an injectable clock (no real
  sleeps in tests).
- ``compile_cache`` — the JAX persistent compilation cache at
  ``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache`` (cold-start
  compiles paid once across processes; docs/SERVING.md).
"""

from .perf import PerfCounters, global_perf, profile_trace  # noqa: F401
from .debug import debug_mode, verification_enabled  # noqa: F401
from .config import (  # noqa: F401
    Config,
    ErasureCodeProfileStore,
    Option,
    global_config,
)
from .log import dout, get_level, set_level  # noqa: F401
from .errors import (  # noqa: F401
    CephTpuError,
    RetryExhausted,
    ScrubError,
    TransientBackendError,
    UnrecoverableError,
)
from .retry import (  # noqa: F401
    FakeClock,
    RetryPolicy,
    RetryStats,
    SystemClock,
    retry_call,
)
from .compile_cache import (  # noqa: F401
    cache_entries,
    compile_cache_dir,
    initialize_compile_cache,
    install_cache_monitor,
)
