"""Device-plane cost/roofline attribution for compiled programs.

The telemetry plane (ISSUE 6) answered "how long did the host wait" —
dispatch histograms per call site.  This module answers the question
underneath: **how close is each compiled program to the HBM roofline**.
We report 185.7 GB/s packed encode without knowing whether that is 60%
or 95% of what the chip can stream, and the 8–38× composite-decode gap
(ROADMAP item 2) has no per-program byte/FLOP attribution saying
*where* shec/clay lose — exactly the kernel-level utilization analysis
Ragged Paged Attention uses to motivate its TPU kernels (PAPERS.md,
arxiv 2604.15464), and the per-program cost accounting the XOR-
scheduling work (arxiv 2108.02692) needs to prove a lowering win.

One :class:`ProgramProfiler` holds a :class:`ProgramRecord` per
compiled program:

- **cost side** — XLA's own cost model, captured via
  ``jax.stages.Lowered.cost_analysis()``.  Capturing lowers (traces)
  the program but **never backend-compiles** — the warm==0 recompile
  sentinels in analysis/jaxpr_audit.py stay green by construction,
  which is why capture can ride the hot engine seams
  (codes/engine.py, crush/bulk.py) at first eager dispatch.
- **measured side** — a LatencyHistogram fed by the same dispatch the
  telemetry plane already times; the profiler clock is injectable so
  FakeClock runs produce byte-identical attribution rows.
- **join** — :meth:`ProgramProfiler.attribution_rows` emits one row
  per (program, plugin, pattern, engine tier, device count):
  bytes/FLOPs from the cost model, measured p50/p99, achieved GB/s,
  the model-bound GB/s at the HBM roofline, and utilization %
  (docs/OBSERVABILITY.md "Device-plane profiler" has the formulas).

When no XLA cost is reachable (the ``--device host`` bench rows),
:func:`analytic_matrix_cost` supplies the GF(2^8)
matrix-apply model so host-only rounds still carry attribution rows
with honest ``source="analytic"`` provenance.

Host-side only by construction at module scope: jax is imported
lazily inside capture paths, and ``profiler_selftest`` (the
``telemetry.profiler_selftest`` host-tier audit entry) drives the
whole attribution join on synthetic records with ZERO compiles.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Dict, List, Optional, Tuple

from .histogram import LatencyHistogram
from .metrics import series_name
from ..utils.detcheck import default_clock
from ..utils.locks import make_lock

# Published peak HBM bandwidth per jax ``device_kind``, GB/s — the
# roofline denominator.  A kind with no entry has no roofline: its
# rows carry utilization null ("not measured"), never a default.
DEVICE_PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0,
                    "source": "Google Cloud TPU v5e documentation"},
}

TOP_N = 10  # hot-program list length in to_dict()


class _SystemClock:
    def monotonic(self) -> float:
        return time.monotonic()


# CEPH_TPU_PROFILE=0 disables the XLA cost-capture side (a capture
# lowers the program once — microseconds for EC programs, seconds for
# a 10k-OSD fused CRUSH rule); the measured histograms keep recording
# either way, so rows degrade to latency-only instead of vanishing.
_capture_enabled = os.environ.get(
    "CEPH_TPU_PROFILE", "1").strip() != "0"


def capture_enabled() -> bool:
    return _capture_enabled


def set_capture_enabled(on: bool) -> bool:
    """Toggle XLA cost capture (tests / overhead probes); returns the
    previous setting."""
    global _capture_enabled
    prev = _capture_enabled
    _capture_enabled = on
    return prev


def resolve_peak_gbps(device_kind: Optional[str]) -> Optional[float]:
    """The HBM roofline peak of ``device_kind`` (jax's
    ``device.device_kind``), or None when it has no published peak."""
    peak = DEVICE_PEAKS.get(device_kind)
    return None if peak is None else float(peak["hbm_gbps"])


def analytic_matrix_cost(batch: int, rows: int, cols: int,
                         chunk_bytes: int) -> Dict[str, float]:
    """GF(2^8) matrix-apply cost model (the host-tier stand-in for
    XLA cost_analysis): ``out[r] = xor_c M[r,c] * in[c]`` over
    ``chunk_bytes``-byte chunks — one GF multiply + one XOR per
    (row, col, byte), input read once, output written once."""
    gf_ops = float(batch) * rows * cols * chunk_bytes
    return {"flops": 2.0 * gf_ops,
            "bytes accessed": float(batch) * (rows + cols) * chunk_bytes}


def analytic_xor_schedule_cost(batch: int, rows: int, cols: int,
                               chunk_bytes: int,
                               vpu_ops: int) -> Dict[str, float]:
    """Cost model for an XOR-scheduled matrix apply (ISSUE 12,
    ops/xor_schedule.py): the schedule is a straight-line program of
    ``vpu_ops`` full-width vector ops, each touching one chunk-sized
    tile — so flops = batch * vpu_ops * chunk_bytes (byte-ops), while
    the HBM side is unchanged from the dense model (input read once,
    output written once).  This is the "analytic model extended to
    XOR schedules": host-only rounds report the scheduled program's
    REAL op count, so the FLOP reduction the schedule buys is visible
    in the same attribution rows the dense model feeds."""
    return {"flops": float(batch) * vpu_ops * chunk_bytes,
            "bytes accessed": float(batch) * (rows + cols) * chunk_bytes}


def _normalize_cost(cost) -> Optional[Dict[str, float]]:
    """cost_analysis() shapes vary by jax version/stage: a dict at the
    Lowered stage, a one-element list of dicts at Compiled.  Normalize
    to {flops, bytes accessed} floats (absent keys -> 0.0)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes accessed": float(cost.get("bytes accessed", 0.0))}


def _nbytes(args) -> Optional[int]:
    total = 0
    for a in args:
        n = getattr(a, "nbytes", None)
        if n is None:
            return None
        total += int(n)
    return total


@dataclasses.dataclass
class ProgramRecord:
    """One compiled program's attribution state."""

    key: tuple
    name: str
    labels: Dict[str, str]          # plugin/kind/pattern/engine/devices
    device_kind: Optional[str] = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    arg_bytes: Optional[int] = None
    source: str = "none"            # "xla" | "analytic" | "none"
    error: Optional[str] = None
    hist: LatencyHistogram = dataclasses.field(
        default_factory=LatencyHistogram)

    def series(self) -> str:
        return series_name(
            self.name,
            tuple(sorted((str(k), str(v))
                         for k, v in self.labels.items())))


class ProgramProfiler:
    """Process-wide per-program cost/roofline attribution registry.

    Capture is **idempotent per key** (the hot engine seams call it on
    every eager dispatch; only the first lowers) and **never raises**
    into the dispatch path — a capture failure becomes
    ``record.error`` plus a ``profiler_capture_errors`` counter, never
    a failed repair."""

    def __init__(self, clock=None) -> None:
        self.clock = clock if clock is not None \
            else default_clock("telemetry.profiler.ProgramProfiler",
                               _SystemClock)
        self._lock = make_lock("telemetry.profiler.ProgramProfiler._lock")
        self._records: Dict[tuple, ProgramRecord] = {}
        self.captures = 0
        self.capture_errors = 0

    # -- capture ---------------------------------------------------------

    def has(self, key: tuple) -> bool:
        with self._lock:
            return key in self._records

    def capture(self, key: tuple, fn=None, args: tuple = (), *,
                name: str, device_kind: Optional[str] = None,
                cost: Optional[dict] = None,
                arg_bytes: Optional[int] = None,
                **labels) -> ProgramRecord:
        """Register program ``key``, capturing its cost model.

        Exactly one of the cost sources applies: an explicit ``cost``
        dict ({flops, bytes accessed} — the analytic/host path), or a
        jit-compatible ``fn`` + concrete ``args`` which is lowered
        (traced, never backend-compiled) and asked for XLA
        ``cost_analysis()``.  Subsequent calls with the same key are a
        dict-lookup fast path."""
        with self._lock:
            hit = self._records.get(key)
            if hit is not None:
                return hit
        rec = ProgramRecord(
            key=key, name=name,
            labels={str(k): str(v) for k, v in sorted(labels.items())},
            device_kind=device_kind,
            arg_bytes=arg_bytes if arg_bytes is not None
            else _nbytes(args))
        norm = _normalize_cost(cost) if cost is not None else None
        if norm is not None:
            rec.flops = norm["flops"]
            rec.bytes_accessed = norm["bytes accessed"]
            rec.source = "analytic"
        elif fn is not None and _capture_enabled:
            # lower OUTSIDE the lock (tracing a big program takes real
            # time and must not serialize unrelated dispatches); the
            # Lowered-stage cost analysis runs XLA's HLO cost model
            # with ZERO backend compiles, so the recompile sentinels
            # cannot see this.
            try:
                import jax
                if rec.device_kind is None:
                    rec.device_kind = jax.devices()[0].device_kind
                jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
                norm = _normalize_cost(jfn.lower(*args).cost_analysis())
                if norm is not None:
                    rec.flops = norm["flops"]
                    rec.bytes_accessed = norm["bytes accessed"]
                    rec.source = "xla"
            except Exception as e:  # noqa: BLE001 — observability must
                # never fail the dispatch it is riding
                rec.error = f"{type(e).__name__}: {e}"
        with self._lock:
            race = self._records.get(key)
            if race is not None:
                return race
            self._records[key] = rec
            self.captures += 1
            if rec.error is not None:
                self.capture_errors += 1
        from . import metrics as tel
        tel.counter("profiler_captures", source=rec.source)
        if rec.error is not None:
            tel.counter("profiler_capture_errors")
            tel.event("profiler_capture_error", name=name,
                      error=rec.error)
        tel.gauge("profiler_programs", len(self._records))
        return rec

    # -- measured side ---------------------------------------------------

    def observe(self, key: tuple, seconds: float) -> None:
        with self._lock:
            rec = self._records.get(key)
        if rec is not None:
            rec.hist.record(seconds)

    @contextlib.contextmanager
    def timed(self, key: tuple, eager: bool = True):
        """Time one dispatch into the program's histogram.  ``eager=
        False`` (the call site is being traced) records nothing, the
        same discipline as metrics.record_dispatch."""
        from . import metrics as tel
        if not (eager and tel.enabled()):
            yield
            return
        t0 = self.clock.monotonic()
        try:
            yield
        finally:
            self.observe(key, self.clock.monotonic() - t0)

    # -- the join --------------------------------------------------------

    def attribution_rows(self) -> List[dict]:
        """One deterministic row per program: cost model × measured
        dispatch latency × roofline.

        - ``achieved_gbps``   = arg_bytes / p50 (input-byte rate, the
          unit every bench row speaks)
        - ``hbm_gbps``        = bytes_accessed / p50 (modeled HBM
          traffic rate)
        - ``model_bound_gbps``= peak × arg_bytes / bytes_accessed (the
          input-byte rate this program would reach at HBM peak)
        - ``utilization_pct`` = 100 × hbm_gbps / peak
        """
        with self._lock:
            records = sorted(
                self._records.values(),
                key=lambda r: (r.name, tuple(sorted(r.labels.items()))))
        rows = []
        for rec in records:
            p50 = p99 = None
            if rec.hist.count:
                pcts = rec.hist.percentiles()
                p50, p99 = pcts["p50"], pcts["p99"]
            peak = resolve_peak_gbps(rec.device_kind)
            row = {
                "name": rec.name,
                "series": rec.series(),
                "device_kind": rec.device_kind,
                "source": rec.source,
                "flops": rec.flops,
                "bytes_accessed": rec.bytes_accessed,
                "arg_bytes": rec.arg_bytes,
                "calls": rec.hist.count,
                "p50_ms": round(p50 * 1e3, 6) if p50 else None,
                "p99_ms": round(p99 * 1e3, 6) if p99 else None,
                "achieved_gbps": None,
                "hbm_gbps": None,
                "model_bound_gbps": None,
                "utilization_pct": None,
                "flops_per_byte": None,
                "error": rec.error,
            }
            row.update(rec.labels)
            if rec.flops and rec.bytes_accessed:
                row["flops_per_byte"] = round(
                    rec.flops / rec.bytes_accessed, 6)
            if p50:
                if rec.arg_bytes:
                    row["achieved_gbps"] = round(
                        rec.arg_bytes / p50 / 1e9, 6)
                if rec.bytes_accessed:
                    row["hbm_gbps"] = round(
                        rec.bytes_accessed / p50 / 1e9, 6)
            if peak and rec.bytes_accessed:
                if rec.arg_bytes:
                    row["model_bound_gbps"] = round(
                        peak * rec.arg_bytes / rec.bytes_accessed, 6)
                if row["hbm_gbps"] is not None:
                    row["utilization_pct"] = round(
                        100.0 * row["hbm_gbps"] / peak, 4)
            rows.append(row)
        return rows

    def top_programs(self, n: int = TOP_N) -> List[dict]:
        """The hot list: programs by total measured dispatch seconds."""
        with self._lock:
            records = sorted(
                self._records.values(),
                key=lambda r: (-r.hist.sum, r.name,
                               tuple(sorted(r.labels.items()))))
        return [{"series": r.series(),
                 "total_s": round(r.hist.sum, 6),
                 "calls": r.hist.count}
                for r in records[:n] if r.hist.count]

    def to_dict(self) -> dict:
        """The perf-dump ``profile`` section (schema.py validates)."""
        rows = self.attribution_rows()
        return {"programs": len(rows),
                "captures": self.captures,
                "capture_errors": self.capture_errors,
                "rows": rows,
                "top": self.top_programs()}

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self.captures = 0
            self.capture_errors = 0


_global: Optional[ProgramProfiler] = None
_global_lock = make_lock("telemetry.profiler._global_lock")


def global_profiler() -> ProgramProfiler:
    global _global
    with _global_lock:
        if _global is None:
            _global = ProgramProfiler()
        return _global


def set_global_profiler(profiler: Optional[ProgramProfiler]
                        ) -> Optional[ProgramProfiler]:
    """Swap the process profiler (tests); returns the previous one."""
    global _global
    with _global_lock:
        prev = _global
        _global = profiler
        return prev


# ----------------------------------------------------------------------
# entry-point sweep: an attribution row for EVERY jit-tier audited
# entry point (the acceptance gate perf_dump --profile enforces)

def profile_entrypoints(filters: Tuple[str, ...] = (),
                        measure: bool = True, repeats: int = 3,
                        profiler: Optional[ProgramProfiler] = None,
                        ) -> Tuple[List[dict], List[str]]:
    """Walk the tpu-audit registry (analysis/entrypoints.py), capture
    the XLA cost model for every jit-tier entry's representative
    program, and (with ``measure``) time ``repeats`` real dispatches
    on the profiler clock.  Returns ``(rows, failed)`` — an entry that
    cannot produce a row lands in ``failed`` so perf_dump --profile
    can fail loudly instead of shipping a partial table.

    Cost capture is lower-only (zero backend compiles); ``measure``
    dispatches do compile, once, exactly like the recompile sentinel's
    cold run."""
    from ..analysis.entrypoints import registry

    prof = profiler if profiler is not None else global_profiler()
    failed: List[str] = []
    for ep in registry():
        if ep.kind != "jit":
            continue
        if filters and not any(f in ep.name for f in filters):
            continue
        try:
            built = ep.build()
            key = ("entry", ep.name)
            with built.scope():
                rec = prof.capture(key, built.fn, built.args,
                                   name=ep.name, plugin=ep.family,
                                   kind="entrypoint", engine="xla",
                                   devices=1)
            if rec.bytes_accessed is None:
                failed.append(f"{ep.name}: {rec.error or 'no cost'}")
                continue
            if measure:
                import jax
                for _ in range(repeats):
                    t0 = prof.clock.monotonic()
                    with built.scope():
                        out = built.fn(*built.args)
                        jax.block_until_ready(out)
                    prof.observe(key, prof.clock.monotonic() - t0)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            failed.append(f"{ep.name}: {type(e).__name__}: {e}")
    return prof.attribution_rows(), failed


# ----------------------------------------------------------------------
# the tpu-audit host-tier workload

class _Tick:
    """Deterministic auto-advancing clock (1 ms per read)."""

    def __init__(self, step: float = 0.001) -> None:
        self.now = 0.0
        self.step = step

    def monotonic(self) -> float:
        self.now += self.step
        return self.now


def profiler_selftest() -> dict:
    """The ``telemetry.profiler_selftest`` host-tier audit entry: the
    whole capture → observe → attribution-join → section-dump pipeline
    on an ISOLATED profiler with synthetic (analytic) costs and a
    deterministic tick clock.  Must trigger ZERO jax compiles and
    return only host data — enforced forever by the jaxpr-audit
    recompile sentinel."""
    import json

    from .schema import validate_profile_section

    prof = ProgramProfiler(clock=_Tick())
    key = ("selftest", "encode")
    prof.capture(key, name="selftest.encode", device_kind="TPU v5 lite",
                 cost=analytic_matrix_cost(4, 3, 8, 4096),
                 arg_bytes=4 * 8 * 4096,
                 plugin="selftest", kind="serve-encode",
                 engine="device", devices=1)
    prof.capture(key, name="selftest.encode")  # idempotent fast path
    with prof.timed(key):
        pass
    prof.observe(key, 0.002)
    rows = prof.attribution_rows()
    if len(rows) != 1:
        raise AssertionError(f"selftest expected 1 row, got {len(rows)}")
    row = rows[0]
    for field in ("flops", "bytes_accessed", "p50_ms",
                  "achieved_gbps", "utilization_pct"):
        if not isinstance(row[field], (int, float)):
            raise AssertionError(f"selftest row missing {field}: {row}")
    section = prof.to_dict()
    errors = validate_profile_section("profile", section)
    if errors:
        raise AssertionError(f"profile section invalid: {errors}")
    if json.dumps(section, sort_keys=True) != \
            json.dumps(prof.to_dict(), sort_keys=True):
        raise AssertionError("profile section is not deterministic")
    return section


__all__ = ["DEVICE_PEAKS", "ProgramProfiler", "ProgramRecord",
           "analytic_matrix_cost", "analytic_xor_schedule_cost",
           "capture_enabled", "global_profiler",
           "profile_entrypoints", "profiler_selftest",
           "resolve_peak_gbps", "set_capture_enabled",
           "set_global_profiler"]
