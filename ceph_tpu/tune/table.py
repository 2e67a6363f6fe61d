"""The persisted best-config table — what the autotuner writes and the
engine consults (ISSUE 14, ROADMAP item 2).

Every performance-critical constant in the data plane was hand-picked
until this module: the Pallas row-tile cap, the MXU/XOR/dense cutover
thresholds, the CSE candidate horizon, the serve batch rung ladder,
the mesh fan-out width.  The autotuner (tune/sweep.py +
tools/autotune.py) sweeps a bounded declarative space (tune/space.py)
with the two measurement modes the profiler already owns and persists
the winners here, in a **versioned, schema-validated JSON table** —
the same spirit as the JAX persistent compilation cache
(utils/compile_cache.py): tuned once, reused by every later process.

Keying.  One entry per *tuning key*
``(plugin profile, pattern kind, engine tier, layout, device_count,
batch rung)`` — the same coordinates the PatternCache and the
profiler's attribution rows speak.  Process-wide parameters (the rung
ladder, the cutover thresholds) use ``"*"`` wildcards in the slots
they do not discriminate on; per-matrix engine pins carry a digest of
the static matrix in the profile slot (``m:<sha1-12>``), because the
engine-selection table sees matrices, not plugin names.

Staleness guard.  Every entry records the environment it was tuned on
— ``{platform, device_count, jax_version, table_schema_version}`` —
and :meth:`BestConfigTable.lookup` ignores it (with a
``tune_config_stale`` telemetry counter and a once-per-key
``tune_config_stale`` event) when any of them mismatches the CURRENT
process: a table tuned on one topology can never mis-configure
another.  Missing/stale/mismatched entries fall back to today's
hand-picked constants byte-identically (the consultation seams all
treat ``None`` as "use the default").

Consultation happens at **program-build time** (inside the jit
wrappers' static arguments and the PatternCache builders), so a table
installed before warmup causes zero warm recompiles — the warm==0
audit sentinels stay green with a tuned table installed, which
tests/test_autotune.py pins.  ``install_table`` therefore clears the
PatternCache (and the schedule-probe caches): programs built under
the OLD config must rebuild once under the new one instead of serving
stale traces.

``CEPH_TPU_TUNE_TABLE=<path>`` auto-loads a table at first
consultation; numpy-only at import time (no jax), so the host tier
and the audit tooling can use it in jax-free environments.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
from typing import Dict, List, Optional, Tuple

from ..utils.locks import make_lock

TABLE_SCHEMA_VERSION = 1
ENV_KNOB = "CEPH_TPU_TUNE_TABLE"

# the tuning-key slots, in serialization order (ISSUE 14)
KEY_FIELDS = ("profile", "kind", "engine", "layout", "device_count",
              "rung")

# entry-env fields the staleness guard compares (ISSUE 14 satellite)
ENV_FIELDS = ("platform", "device_count", "jax_version",
              "table_schema_version")


def tuning_key(profile: str = "*", kind: str = "", engine: str = "*",
               layout: str = "*", device_count: int = 1,
               rung: int = 0) -> Tuple:
    """The hashable tuning key (ISSUE 14): ``(plugin profile, pattern
    kind, engine tier, layout, device_count, batch rung)``."""
    if not kind:
        raise ValueError("tuning key needs a kind")
    return (str(profile), str(kind), str(engine), str(layout),
            int(device_count), int(rung))


def key_str(key: Tuple) -> str:
    """JSON dict-key serialization of a tuning key."""
    return "|".join(str(p) for p in key)


def parse_key(s: str) -> Tuple:
    parts = s.split("|")
    if len(parts) != len(KEY_FIELDS):
        raise ValueError(f"tuning key {s!r} must have "
                         f"{len(KEY_FIELDS)} |-separated slots")
    return (parts[0], parts[1], parts[2], parts[3], int(parts[4]),
            int(parts[5]))


def key_hash(key: Tuple) -> str:
    """Short stable digest of one tuning key (bench-row provenance)."""
    return hashlib.sha1(key_str(key).encode()).hexdigest()[:12]


@functools.lru_cache(maxsize=512)
def matrix_digest(matrix_t: tuple) -> str:
    """Digest of a static matrix tuple — the profile-slot identity for
    per-matrix engine pins (``m:<digest>``).  lru-cached because the
    engine-selection table consults it per dispatch."""
    return hashlib.sha1(repr(matrix_t).encode()).hexdigest()[:12]


def profile_str(plugin: str, profile: Dict[str, str]) -> str:
    """Canonical plugin-profile string for the profile slot."""
    body = ",".join(f"{k}={v}" for k, v in
                    sorted((str(k), str(v)) for k, v in profile.items()))
    return f"{plugin}:{body}"


# ----------------------------------------------------------------------
# current-environment probe (what the staleness guard compares against)

_env_lock = make_lock("tune.table._env_lock")
_env_cache: Optional[dict] = None


def current_env() -> dict:
    """The CURRENT process environment the staleness guard compares
    entries against.  Never *initializes* a jax backend (host-only
    callers stay jax-free): platform/device_count read from an
    already-live backend only, else the host defaults."""
    global _env_cache
    with _env_lock:
        if _env_cache is not None:
            return dict(_env_cache)
    platform, device_count, jax_version = "cpu", 1, None
    backend_live = False
    try:
        import sys
        jax_mod = sys.modules.get("jax")
        if jax_mod is None:
            import jax as jax_mod  # import is safe; init is not
        jax_version = jax_mod.__version__
        from jax._src import xla_bridge as xb  # peek, no init
        if getattr(xb, "_backends", None):
            backend_live = True
            platform = jax_mod.default_backend()
            device_count = jax_mod.device_count()
    except Exception:  # noqa: BLE001 — probing must never raise
        pass
    env = {"platform": platform, "device_count": device_count,
           "jax_version": jax_version,
           "table_schema_version": TABLE_SCHEMA_VERSION}
    if backend_live:
        # cache only once a backend is live: before init, a later
        # backend can still change the answer (a host-tier consult
        # must not freeze "cpu" into a process about to dial a TPU)
        with _env_lock:
            _env_cache = env
    return dict(env)


def _invalidate_env_cache() -> None:
    global _env_cache
    with _env_lock:
        _env_cache = None


# ----------------------------------------------------------------------
# the table

def validate_table(d: object) -> List[str]:
    """Schema errors for a table dict ([] = valid).  Shares the
    stdlib-validator spirit of telemetry/schema.py: loud, specific,
    no external deps."""
    errors: List[str] = []
    if not isinstance(d, dict):
        return [f"table must be a dict, got {type(d).__name__}"]
    if d.get("table_schema_version") != TABLE_SCHEMA_VERSION:
        errors.append(
            f"table_schema_version {d.get('table_schema_version')!r} "
            f"!= {TABLE_SCHEMA_VERSION}")
    entries = d.get("entries")
    if not isinstance(entries, dict):
        return errors + ["entries must be a dict"]
    for ks, entry in entries.items():
        try:
            parse_key(ks)
        except (ValueError, TypeError) as e:
            errors.append(f"bad key {ks!r}: {e}")
            continue
        if not isinstance(entry, dict):
            errors.append(f"{ks}: entry must be a dict")
            continue
        if not isinstance(entry.get("config"), dict):
            errors.append(f"{ks}: missing config dict")
        env = entry.get("env")
        if not isinstance(env, dict):
            errors.append(f"{ks}: missing env dict")
        else:
            for f in ENV_FIELDS:
                if f not in env:
                    errors.append(f"{ks}: env missing {f}")
        if entry.get("mode") not in ("analytic", "timed"):
            errors.append(f"{ks}: mode must be analytic|timed")
    return errors


class BestConfigTable:
    """The versioned best-config table: tuning key -> winning config,
    with per-entry environment stamps and scores.

    Thread-safe for the read path (``lookup`` — the dispatch seams);
    writers (the sweeps) are single-threaded by construction."""

    def __init__(self, env: Optional[dict] = None) -> None:
        self.entries: Dict[str, dict] = {}
        self._env = dict(env) if env is not None else None
        self._stale_warned: set = set()
        self._lock = make_lock("tune.table.BestConfigTable._lock")

    def env(self) -> dict:
        """The environment NEW entries are stamped with (the declared
        sweep environment, or the current process env)."""
        # current_env() is probed OUTSIDE the lock (it may touch jax
        # device enumeration); first memoized writer wins
        if self._env is None:
            probed = current_env()
            with self._lock:
                if self._env is None:
                    self._env = probed
        with self._lock:
            return dict(self._env)

    # -- write ----------------------------------------------------------

    def set(self, key: Tuple, config: dict, *, mode: str,
            score: Optional[float] = None,
            baseline_score: Optional[float] = None,
            baseline_config: Optional[dict] = None) -> None:
        if mode not in ("analytic", "timed"):
            raise ValueError(f"mode {mode!r} must be analytic|timed")
        entry = {
            "config": dict(config),
            "env": self.env(),
            "mode": mode,
            "score": score,
            "baseline_score": baseline_score,
        }
        if baseline_config is not None:
            entry["baseline_config"] = dict(baseline_config)
        with self._lock:
            self.entries[key_str(key)] = entry

    # -- read (the consultation seam) -----------------------------------

    def lookup(self, key: Tuple) -> Optional[dict]:
        """The entry's config when its environment stamp matches the
        current process, else None — counted and evented as
        ``tune_config_stale`` so a topology mismatch is observable,
        never silent (ISSUE 14 staleness guard)."""
        ks = key_str(key)
        with self._lock:
            entry = self.entries.get(ks)
        if entry is None:
            return None
        env = entry.get("env") or {}
        now = current_env()
        mismatched = [f for f in ENV_FIELDS if env.get(f) != now.get(f)]
        if mismatched:
            from ..telemetry import metrics as tel
            tel.counter("tune_config_stale")
            with self._lock:
                first = ks not in self._stale_warned
                self._stale_warned.add(ks)
            if first:
                tel.event("tune_config_stale", key=ks,
                          mismatched=",".join(mismatched),
                          entry_env=json.dumps(env, sort_keys=True),
                          current_env=json.dumps(now, sort_keys=True))
            return None
        return dict(entry["config"])

    # -- persistence -----------------------------------------------------

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "table_schema_version": TABLE_SCHEMA_VERSION,
                "entries": {k: json.loads(json.dumps(v))
                            for k, v in sorted(self.entries.items())},
            }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, indent=1)

    @classmethod
    def from_dict(cls, d: dict) -> "BestConfigTable":
        errors = validate_table(d)
        if errors:
            raise ValueError("invalid best-config table: "
                             + "; ".join(errors[:5]))
        t = cls()
        with t._lock:
            t.entries = {str(k): dict(v)
                         for k, v in d["entries"].items()}
        return t

    def save(self, path: str) -> None:
        """Atomic write: a crash mid-write never truncates the table."""
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(self.to_json())
            f.write("\n")
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "BestConfigTable":
        with open(path, encoding="utf-8") as f:
            return cls.from_dict(json.load(f))

    def content_hash(self) -> Optional[str]:
        """Digest of the tuned key set + configs (bench-row
        provenance: the ``tune_key_hash`` field)."""
        with self._lock:
            if not self.entries:
                return None
        return hashlib.sha1(self.to_json().encode()).hexdigest()[:12]

    def __len__(self) -> int:
        with self._lock:
            return len(self.entries)


# ----------------------------------------------------------------------
# the process-wide installed table (what the seams consult)

_lock = make_lock("tune.table._lock")
_active: Optional[BestConfigTable] = None
_env_resolved = False
_generation = 0


def _clear_consult_caches() -> None:
    """Programs built under the OLD config must rebuild under the new
    one: clear the PatternCache (the engine's program identity space)
    and the schedule-probe caches.  Best-effort — a half-imported
    process (the jax-free audit tier) just skips the missing ones."""
    try:
        from ..codes.engine import global_pattern_cache
        global_pattern_cache().clear()
    except Exception:  # noqa: BLE001 — cache clearing is best-effort
        pass
    try:
        from ..ops import xor_schedule
        xor_schedule.probe_schedule.cache_clear()
    except Exception:  # noqa: BLE001
        pass


def install_table(table: Optional[BestConfigTable],
                  clear_caches: bool = True
                  ) -> Optional[BestConfigTable]:
    """Install (or, with None, uninstall) the process best-config
    table; returns the previous one.  Bumps the consultation
    generation and (by default) clears the program caches, so tuned
    configs land at the next program build — after which the warm
    path compiles nothing (the zero-warm-recompile contract)."""
    global _active, _env_resolved, _generation
    with _lock:
        prev = _active
        _active = table
        _env_resolved = True
        _generation += 1
    _invalidate_env_cache()
    if clear_caches:
        _clear_consult_caches()
    return prev


def active_table() -> Optional[BestConfigTable]:
    """The installed table, resolving the ``CEPH_TPU_TUNE_TABLE`` env
    knob on first call (a load failure logs + counts, never raises —
    the engine must keep running on defaults)."""
    global _active, _env_resolved
    with _lock:
        if _env_resolved:
            return _active
        _env_resolved = True
    path = os.environ.get(ENV_KNOB, "").strip()
    if not path:
        return _active
    try:
        table = BestConfigTable.load(path)
    except (OSError, ValueError) as e:
        from ..telemetry import metrics as tel
        from ..utils.log import dout
        dout("ec", 1, f"tune table {path!r} unusable "
                      f"({type(e).__name__}: {e}); running on defaults")
        tel.counter("tune_table_load_errors")
        tel.event("tune_table_load_error", path=path,
                  error=f"{type(e).__name__}: {e}")
        return _active
    with _lock:
        _active = table
    return table


def generation() -> int:
    with _lock:
        return _generation


def consult(kind: str, profile: str = "*", engine: str = "*",
            layout: str = "*", rung: int = 0,
            device_count: Optional[int] = None) -> Optional[dict]:
    """THE consultation seam: the tuned config for one key, or None
    (= use today's constant, byte-identically).  Cheap by design — a
    dict lookup plus the env compare — because the engine-selection
    table calls it per dispatch."""
    table = active_table()
    if table is None:
        return None
    dc = device_count if device_count is not None \
        else current_env()["device_count"]
    return table.lookup(tuning_key(profile, kind, engine, layout,
                                   dc, rung))


def active_source() -> Tuple[str, Optional[str]]:
    """``("tuned", <table content hash>)`` when a non-empty table is
    installed, else ``("default", None)`` — every bench workload row
    carries this pair (metric_version 11)."""
    table = active_table()
    if table is None or not len(table):
        return "default", None
    return "tuned", table.content_hash()


@dataclasses.dataclass
class _Override:
    prev: Optional[BestConfigTable]


class scoped_table:
    """Context manager installing a table for a block (the timed
    sweep's candidate evaluation; tests) and restoring the previous
    one — including "nothing installed"."""

    def __init__(self, table: Optional[BestConfigTable],
                 clear_caches: bool = True) -> None:
        self.table = table
        self.clear_caches = clear_caches
        self._ov: Optional[_Override] = None

    def __enter__(self) -> Optional[BestConfigTable]:
        self._ov = _Override(install_table(self.table,
                                           self.clear_caches))
        return self.table

    def __exit__(self, *exc) -> None:
        install_table(self._ov.prev, self.clear_caches)


__all__ = [
    "BestConfigTable", "ENV_FIELDS", "ENV_KNOB", "KEY_FIELDS",
    "TABLE_SCHEMA_VERSION", "active_source", "active_table", "consult",
    "current_env", "generation", "install_table", "key_hash",
    "key_str", "matrix_digest", "parse_key", "profile_str",
    "scoped_table", "tuning_key", "validate_table",
]
