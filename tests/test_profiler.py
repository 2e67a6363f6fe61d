"""Device-plane profiler + flight recorder + perf-regression sentinel
(ISSUE 10).

The load-bearing claims pinned here:

- XLA cost capture (``Lowered.cost_analysis``) triggers ZERO backend
  compiles — the warm==0 recompile sentinels cannot be disturbed by
  profiling, which is what lets capture ride the hot engine seams;
- the attribution join is exact arithmetic (utilization ==
  100 · bytes/(p50 · peak)) and byte-deterministic;
- every audited engine/serve program dispatched eagerly gets an
  attribution row keyed per (plugin, pattern, engine tier, devices,
  batch), while traced dispatches record nothing;
- the flight recorder freezes a schema-valid, byte-identical-across-
  reruns post-mortem blob at each trigger: UnrecoverableError
  construction, CrashPoint fires, armed recompile-budget trips, and
  serving SLO burn-rate breaches;
- tools/bench_diff.py flags a synthetic 20% headline regression (red
  fixture) and passes rc0 on the repo's real BENCH_* trajectory.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib

import numpy as np
import pytest

from ceph_tpu import telemetry
from ceph_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    ProgramProfiler,
    SpanTracer,
    set_global_flight_recorder,
    set_global_metrics,
    set_global_profiler,
    set_global_tracer,
    validate_dump,
    validate_flight_dump,
)
from ceph_tpu.telemetry.profiler import (
    analytic_matrix_cost,
    profile_entrypoints,
    resolve_peak_gbps,
)
from ceph_tpu.utils.errors import UnrecoverableError
from ceph_tpu.utils.retry import FakeClock

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", REPO_ROOT / "tools" / "bench_diff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Tick:
    def __init__(self, step=0.001):
        self.now, self.step = 0.0, step

    def monotonic(self):
        self.now += self.step
        return self.now


# ----------------------------------------------------------------------
# cost capture + the attribution join

def test_attribution_math_is_exact():
    prof = ProgramProfiler(clock=FakeClock())
    key = ("t", "p")
    prof.capture(key, name="t.p", device_kind="TPU v5 lite",
                 cost={"flops": 1000.0, "bytes accessed": 8190.0},
                 arg_bytes=4095, plugin="x", kind="encode",
                 engine="device", devices=1)
    prof.observe(key, 0.001)              # 1 ms
    prof.observe(key, 0.001)
    prof.observe(key, 0.004)
    (row,) = prof.attribution_rows()
    assert row["calls"] == 3
    assert row["p50_ms"] == pytest.approx(1.0, rel=0.02)
    p50_s = row["p50_ms"] / 1e3
    # achieved = arg_bytes/p50; hbm = bytes/p50; util = 100*hbm/peak;
    # model_bound = peak * arg_bytes / bytes  (peak: v5e = 819 GB/s)
    assert row["achieved_gbps"] == pytest.approx(
        4095 / p50_s / 1e9, rel=1e-6)
    assert row["hbm_gbps"] == pytest.approx(
        8190 / p50_s / 1e9, rel=1e-6)
    assert row["utilization_pct"] == pytest.approx(
        100.0 * row["hbm_gbps"] / 819.0, rel=1e-3)
    assert row["model_bound_gbps"] == pytest.approx(
        819.0 * 4095 / 8190, rel=1e-6)
    assert row["flops_per_byte"] == pytest.approx(1000 / 8190,
                                                  rel=1e-6)


def test_capture_is_idempotent_and_deterministic():
    prof = ProgramProfiler(clock=FakeClock())
    key = ("k",)
    r1 = prof.capture(key, name="n", cost={"flops": 1.0,
                                           "bytes accessed": 2.0})
    r2 = prof.capture(key, name="other-ignored")
    assert r1 is r2 and prof.captures == 1
    a = json.dumps(prof.to_dict(), sort_keys=True)
    b = json.dumps(prof.to_dict(), sort_keys=True)
    assert a == b


def test_xla_capture_costs_zero_backend_compiles():
    """The enabling property of the whole design: lower-only capture
    never backend-compiles, so the recompile sentinels stay green."""
    import jax
    import jax.monitoring

    compiles = [0]

    def listener(name, duration, **kw):
        if "backend_compile" in name:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    prof = ProgramProfiler(clock=FakeClock())
    x = np.zeros((4, 8, 512), np.uint8)
    before = compiles[0]
    rec = prof.capture(("xla-test",), lambda a: a ^ a, (x,),
                       name="xla.test", plugin="t", kind="t",
                       engine="device", devices=1)
    assert compiles[0] == before          # capture compiled NOTHING
    assert rec.source == "xla"
    assert rec.flops is not None and rec.bytes_accessed > 0
    assert rec.arg_bytes == x.nbytes


def test_capture_failure_never_raises():
    prof = ProgramProfiler(clock=FakeClock())

    def broken(a):
        raise RuntimeError("boom at trace time")

    rec = prof.capture(("bad",), broken, (np.zeros(4, np.uint8),),
                       name="bad.prog")
    assert rec.error is not None and rec.source == "none"
    (row,) = prof.attribution_rows()
    assert row["error"] and row["flops"] is None


def test_analytic_model_and_peak_resolution():
    cost = analytic_matrix_cost(4, 3, 8, 1024)
    assert cost["flops"] == 2.0 * 4 * 3 * 8 * 1024
    assert cost["bytes accessed"] == 4 * 11 * 1024
    # peaks are keyed by jax device_kind; a platform name, the CPU or
    # an unknown chip has no roofline ("not measured"), never a default
    assert resolve_peak_gbps("TPU v5 lite") == 819.0
    for kind in ("tpu", "cpu", "TPU v4", None):
        assert resolve_peak_gbps(kind) is None


def test_unknown_device_kind_rows_carry_no_utilization():
    prof = ProgramProfiler(clock=FakeClock())
    prof.capture(("c",), name="c", device_kind="cpu",
                 cost={"flops": 1.0, "bytes accessed": 2.0}, arg_bytes=1)
    prof.observe(("c",), 0.001)
    (row,) = prof.attribution_rows()
    assert row["device_kind"] == "cpu"
    assert row["achieved_gbps"] is not None
    assert row["utilization_pct"] is None
    assert row["model_bound_gbps"] is None


def test_top_programs_orders_by_total_seconds():
    prof = ProgramProfiler(clock=FakeClock())
    for name, secs in (("a", 0.001), ("b", 0.010), ("c", 0.002)):
        prof.capture((name,), name=name,
                     cost={"flops": 1.0, "bytes accessed": 1.0})
        prof.observe((name,), secs)
    prof.capture(("never-called",), name="never",
                 cost={"flops": 1.0, "bytes accessed": 1.0})
    top = prof.top_programs()
    assert [t["series"] for t in top] == ["b", "c", "a"]  # no zero-call


# ----------------------------------------------------------------------
# the engine seams feed the profiler

@pytest.mark.parametrize("seam", ["serve_encode", "fused_repair"])
def test_engine_dispatch_rows_and_traced_silence(seam):
    """Both engine seams run through one eager dispatch wrapper and
    keep their own names: the profiler row (``engine.serve_dispatch``
    kind ``serve-encode``; ``engine.fused_repair`` kind
    ``fused-repair``), one row per batch rung, and the dispatch
    histogram (``serve_dispatch{op,plugin}``;
    ``engine_fused_repair_dispatch{plugin}``, no ``op``).  A traced
    dispatch records nothing."""
    import jax

    from ceph_tpu.codes.engine import (fused_repair_call,
                                       serve_dispatch_call)
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"})
    plugin = type(ec).__name__
    if seam == "serve_encode":
        fn = serve_dispatch_call(ec, "encode")
        rows_in, name, kind = 4, "engine.serve_dispatch", "serve-encode"
        series = f"serve_dispatch_seconds{{op=encode,plugin={plugin}}}"
    else:
        fn = fused_repair_call(ec, (0, 1, 2, 4, 5), (3,))
        rows_in, name, kind = 5, "engine.fused_repair", "fused-repair"
        series = f"engine_fused_repair_dispatch_seconds{{plugin={plugin}}}"
    data = np.random.default_rng(3).integers(
        0, 256, (4, rows_in, 1024), np.uint8)
    prev = set_global_profiler(ProgramProfiler())
    prev_reg = set_global_metrics(MetricsRegistry())
    try:
        jax.block_until_ready(fn(jax.device_put(data)))
        jax.block_until_ready(fn(jax.device_put(data)))
        rows = telemetry.global_profiler().attribution_rows()
        (row,) = [r for r in rows if r["name"] == name]
        assert row["kind"] == kind
        assert row["pattern"] == ("e" if seam == "serve_encode"
                                  else "e3")
        assert row["batch"] == "4" and row["devices"] == "1"
        assert row["source"] == "xla" and row["bytes_accessed"] > 0
        assert row["calls"] == 2
        (body,) = telemetry.global_metrics().dump().values()
        dispatch = {k: v for k, v in body.items()
                    if k.startswith(("serve_dispatch_",
                                     "engine_fused_repair_dispatch_"))}
        assert set(dispatch) == {
            series, series.replace("_seconds{", "_calls{")}
        assert dispatch[series]["count"] == 2
        # a DIFFERENT batch rung through the same cached program gets
        # its own row (per-shape attribution)
        jax.block_until_ready(fn(jax.device_put(data[:2])))
        rows = telemetry.global_profiler().attribution_rows()
        assert len([r for r in rows if r["name"] == name]) == 2
        # traced dispatch records nothing: the jaxpr stays
        # profiler-free exactly like it stays telemetry-free
        set_global_profiler(ProgramProfiler())
        set_global_metrics(MetricsRegistry())
        jitted = jax.jit(lambda a: fn(a))
        jax.block_until_ready(jitted(jax.device_put(data)))
        assert telemetry.global_profiler().attribution_rows() == []
        (body,) = telemetry.global_metrics().dump().values()
        assert series not in body
    finally:
        set_global_profiler(prev)
        set_global_metrics(prev_reg)


def test_profile_entrypoints_subset_rows_complete():
    """The perf-dump --profile acceptance property on a fast subset:
    every swept jit entry produces a row with cost AND measured
    fields.  (The full 38-entry sweep runs as the test_full.sh
    profiler coverage gate.)"""
    prof = ProgramProfiler(clock=_Tick())
    rows, failed = profile_entrypoints(
        filters=("engine.fused_repair_call", "serve.dispatch",
                 "ops.apply_matrix_best"),
        measure=True, repeats=2, profiler=prof)
    assert failed == []
    entry_rows = [r for r in rows if r["kind"] == "entrypoint"]
    assert len(entry_rows) >= 3
    for row in entry_rows:
        assert row["flops"] is not None, row["name"]
        assert row["bytes_accessed"] > 0
        assert row["calls"] == 2 and row["p50_ms"] > 0
        assert row["achieved_gbps"] > 0
        # a CPU run has no roofline: utilization is "not measured"
        assert row["device_kind"] == "cpu"
        assert row["utilization_pct"] is None


def test_audit_entries_registered_and_compile_free():
    from ceph_tpu.analysis.entrypoints import registry, registry_gaps
    from ceph_tpu.analysis.jaxpr_audit import run_sentinel

    eps = {e.name: e for e in registry()}
    assert len(eps) >= 43 and registry_gaps() == []
    for name in ("telemetry.profiler_selftest",
                 "telemetry.flight_recorder"):
        ep = eps[name]
        assert ep.kind == "host" and ep.trace_budget == 0
        audit = run_sentinel(ep)
        assert audit.ok, [f.render() for f in audit.findings]
        assert audit.cold_compiles == 0 and audit.warm_compiles == 0


# ----------------------------------------------------------------------
# flight recorder

def _fresh_flight_world(clk):
    state = (set_global_tracer(SpanTracer(clock=clk, annotate=False)),
             set_global_metrics(MetricsRegistry(clock=clk)),
             set_global_profiler(ProgramProfiler(clock=clk)),
             set_global_flight_recorder(FlightRecorder(clock=clk)))
    return state


def _restore_flight_world(state):
    tr, reg, prof, rec = state
    set_global_tracer(tr)
    set_global_metrics(reg)
    set_global_profiler(prof)
    set_global_flight_recorder(rec)


def test_unrecoverable_construction_freezes_postmortem():
    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        telemetry.counter("some_counter", 7)
        exc = UnrecoverableError("3 shards lost", shards=[0, 2, 5],
                                 extents=[(0, 4096)])
        rec = telemetry.global_flight_recorder()
        blob = rec.last_dump()
        assert blob is not None and blob["trigger"] == "unrecoverable"
        assert "3 shards lost" in blob["reason"]
        assert blob["context"]["shards"] == [0, 2, 5]
        assert blob["context"]["extents"] == [[0, 4096]]
        assert validate_flight_dump(blob) == []
        reg_name = telemetry.global_metrics().name
        assert blob["metrics"][reg_name]["some_counter"] == 7
        assert blob["metrics_delta"][f"{reg_name}.some_counter"] == 7
        assert exc.shards == (0, 2, 5)    # the hook never mutates
    finally:
        _restore_flight_world(state)


def _unrecoverable_scenario(seed=13, objects=3):
    """Seeded past-budget repair on a FakeClock fresh world; returns
    the flight blob + the unified dump section."""
    from ceph_tpu.chaos import ShardErasure, inject
    from ceph_tpu.codes.engine import (PatternCache,
                                       set_global_pattern_cache)
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    from ceph_tpu.codes.stripe import HashInfo, StripeInfo
    from ceph_tpu.codes.stripe import encode as stripe_encode
    from ceph_tpu.scrub import repair_batched

    clk = FakeClock()
    state = _fresh_flight_world(clk)
    prev_cache = set_global_pattern_cache(PatternCache())
    try:
        telemetry.install_flight_recorder()
        ec = ErasureCodePluginRegistry.instance().factory(
            "jerasure", {"technique": "reed_sol_van",
                         "k": "4", "m": "2"})
        n = ec.get_chunk_count()
        cs = ec.get_chunk_size(4096)
        sinfo = StripeInfo(4, 4 * cs)
        rng = np.random.default_rng(seed)
        stores, hinfos = [], []
        for i in range(objects):
            obj = rng.integers(0, 256, 4 * cs,
                               dtype=np.uint8).tobytes()
            shards = stripe_encode(sinfo, ec, obj)
            h = HashInfo(n)
            h.append(0, shards)
            lost = [0, 1, 2] if i == 0 else [i % n]
            store, _ = inject(shards, [ShardErasure(shards=lost)],
                              seed=seed + i, chunk_size=cs)
            stores.append(store)
            hinfos.append(h)
        with pytest.raises(UnrecoverableError):
            repair_batched(sinfo, ec, stores, hinfos, clock=clk)
        rec = telemetry.global_flight_recorder()
        blob = rec.last_dump()
        section = rec.to_dict()
        return blob, json.dumps(section, sort_keys=True)
    finally:
        set_global_pattern_cache(prev_cache)
        _restore_flight_world(state)


def test_seeded_unrecoverable_dump_byte_identical():
    """The acceptance property: a seeded run with an injected
    UnrecoverableError produces a schema-valid flight dump that is
    byte-identical across reruns."""
    blob1, sec1 = _unrecoverable_scenario()
    blob2, sec2 = _unrecoverable_scenario()
    assert blob1 is not None
    assert validate_flight_dump(blob1) == []
    assert json.dumps(blob1, sort_keys=True) == \
        json.dumps(blob2, sort_keys=True)
    assert sec1 == sec2
    assert blob1["trigger"] == "unrecoverable"
    # the ring held breadcrumbs from before the failure (chaos events
    # ride metrics.event into the recorder)
    kinds = {e["kind"] for e in blob1["entries"]}
    assert "unrecoverable" in kinds


def test_recompile_budget_trip_dumps():
    from ceph_tpu.codes.engine import PatternCache

    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        cache = PatternCache(recompile_budget=1)
        cache.get_or_build(("a",), lambda: 1)
        with pytest.raises(RuntimeError, match="recompile budget"):
            cache.get_or_build(("b",), lambda: 2)
        blob = telemetry.global_flight_recorder().last_dump()
        assert blob is not None
        assert blob["trigger"] == "recompile_budget"
        assert blob["context"]["builds"] == 2
        assert blob["context"]["budget"] == 1
    finally:
        _restore_flight_world(state)


def test_crash_site_trip_dumps():
    from ceph_tpu.chaos import CrashPoint
    from ceph_tpu.utils.errors import InjectedCrash

    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        cp = CrashPoint(site="writeback.after_write")
        with pytest.raises(InjectedCrash):
            cp.visit("writeback.after_write")
        blob = telemetry.global_flight_recorder().last_dump()
        assert blob["trigger"] == "crash_site"
        assert blob["context"]["site"] == "writeback.after_write"
        assert validate_flight_dump(blob) == []
    finally:
        _restore_flight_world(state)


def test_flight_dump_schema_red():
    blob, _ = _unrecoverable_scenario()
    assert validate_flight_dump(blob) == []
    bad = json.loads(json.dumps(blob))
    del bad["metrics_delta"]
    assert any("metrics_delta" in e for e in validate_flight_dump(bad))
    bad2 = json.loads(json.dumps(blob))
    bad2["entries"] = [{"seq": 2, "kind": "a", "t": 0.0},
                       {"seq": 1, "kind": "b", "t": 0.0}]
    assert any("seq-ordered" in e for e in validate_flight_dump(bad2))
    bad3 = json.loads(json.dumps(blob))
    bad3["entries"] = [{"kind": "missing-seq"}]
    assert validate_flight_dump(bad3) != []


def test_unified_dump_optional_sections_validate():
    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        prof = telemetry.global_profiler()
        prof.capture(("p",), name="p", device_kind="TPU v5 lite",
                     cost={"flops": 1.0, "bytes accessed": 2.0},
                     arg_bytes=1)
        UnrecoverableError("x", shards=[1])
        dump = telemetry.dump_all(profile=True, flight=True)
        assert validate_dump(dump) == []
        assert dump["profile"]["programs"] == 1
        assert dump["flight_recorder"]["dump_count"] == 1
        # red: a row losing its utilization key fails the schema
        bad = json.loads(json.dumps(dump))
        del bad["profile"]["rows"][0]["utilization_pct"]
        assert any("utilization_pct" in e for e in validate_dump(bad))
    finally:
        _restore_flight_world(state)


# ----------------------------------------------------------------------
# serving SLO burn-rate monitor

def test_burn_rate_monitor_trips_and_rearms():
    from ceph_tpu.serve.sla import BurnRateMonitor

    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        mon = BurnRateMonitor(budget=0.02, windows=((10, 4.0),))
        # 9 hits: window not full, never trips even at 100% miss
        for _ in range(9):
            assert mon.record("encode", False) == []
        (trip,) = mon.record("encode", False)     # full + over budget
        assert trip["window"] == 10 and trip["miss_rate"] == 1.0
        # sustained breach: armed stays off, no trip storm
        assert mon.record("encode", False) == []
        # drain below threshold -> re-arms -> trips again on the
        # FIRST miss that crosses it (and only once for the streak)
        for _ in range(10):
            assert mon.record("encode", True) == []
        fired = sum(len(mon.record("encode", False))
                    for _ in range(10))
        assert fired == 1
        assert len(mon.trips) == 2
        blob = telemetry.global_flight_recorder().last_dump()
        assert blob["trigger"] == "slo_burn"
        reg = telemetry.global_metrics()
        assert reg.counter_value("serve_slo_burn_trips",
                                 window="10") == 2
    finally:
        _restore_flight_world(state)


def test_sla_recorder_feeds_monitor():
    from ceph_tpu.serve.queue import EcRequest, EcResult
    from ceph_tpu.serve.sla import BurnRateMonitor, SlaRecorder

    clk = FakeClock()
    state = _fresh_flight_world(clk)
    try:
        rec = SlaRecorder(monitor=BurnRateMonitor(
            budget=0.02, windows=((4, 1.0),)))
        data = np.zeros((2, 64), np.uint8)
        for i in range(4):
            req = EcRequest(op="encode", plugin="jerasure",
                            profile={"k": "2", "m": "1"},
                            stripe_size=128, payload=data)
            rec.record(EcResult(request=req, output=data,
                                completed=float(i), queue_wait=0.0,
                                service=0.1, batch_occupancy=1,
                                batch_rung=1,
                                deadline_met=(i % 2 == 0)))
        assert len(rec.monitor.trips) == 1    # 50% misses >= 2% budget
        # the report shape is unchanged (byte-determinism elsewhere
        # depends on it)
        rep = rec.report(elapsed=1.0)
        assert rep["requests"] == 4
        assert "op_classes" in rep and "burn" not in rep
    finally:
        _restore_flight_world(state)


# ----------------------------------------------------------------------
# tools/bench_diff.py — the perf-regression sentinel

def _write_trajectory(tmp_path, prior_value=100.0, current_value=100.0,
                      prior_rows=None, current_rows=None):
    rec = {"metric": "m", "value": prior_value, "unit": "GB/s",
           "git_sha": "aaa", "timestamp": "2026-01-01T00:00:00+00:00"}
    rec.update(prior_rows or {})
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": rec}))
    cur = {"metric": "m", "value": current_value, "unit": "GB/s",
           "git_sha": "bbb", "timestamp": "2026-02-01T00:00:00+00:00"}
    cur.update(current_rows or {})
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))


def test_bench_diff_flags_20pct_headline_regression(tmp_path, capsys):
    bd = _load_bench_diff()
    _write_trajectory(tmp_path, prior_value=100.0, current_value=80.0)
    rc = bd.main(["--repo", str(tmp_path)])
    assert rc == 4
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "headline" in err


def test_bench_diff_within_noise_floor_passes(tmp_path):
    bd = _load_bench_diff()
    _write_trajectory(tmp_path, prior_value=100.0, current_value=90.0)
    assert bd.main(["--repo", str(tmp_path)]) == 0         # 10% < 15%
    # tightening the floor makes the same 10% drop a regression
    assert bd.main(["--repo", str(tmp_path),
                    "--floor", "headline=0.05"]) == 4


def test_bench_diff_normalizes_v1_floats_and_v3_dicts(tmp_path,
                                                      capsys):
    bd = _load_bench_diff()
    _write_trajectory(
        tmp_path, prior_value=100.0, current_value=100.0,
        # v1 shape: bare float rows
        prior_rows={"decode_rows": {"rs": 145.9, "shec": 17.5}},
        # v3+ shape: {gbps, lat_*} dicts; shec regressed 50%
        current_rows={"decode_rows": {
            "rs": {"gbps": 150.0, "lat_p50_ms": 1.0},
            "shec": {"gbps": 8.7, "lat_p50_ms": 9.9}}})
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    # shec/clay decode rows renormalize into the composite_decode
    # category (ISSUE 12) — across the WHOLE trajectory, old records
    # included, so best-prior stays well-defined
    assert report["regressions"] == ["composite_decode:shec"]
    rs = next(r for r in report["rows"] if r["row"] == "decode:rs")
    assert rs["status"] == "ok"


def test_bench_diff_missing_row_is_a_regression(tmp_path):
    bd = _load_bench_diff()
    _write_trajectory(
        tmp_path, prior_value=100.0, current_value=100.0,
        prior_rows={"decode_rows": {"rs": 145.9}},
        current_rows={"decode_rows": {}})
    assert bd.main(["--repo", str(tmp_path)]) == 4


def test_bench_diff_candidate_without_value_is_not_judged(tmp_path,
                                                         capsys):
    """A candidate with no device value (a failed run) is not a
    throughput regression: nothing is judged, rc 0."""
    bd = _load_bench_diff()
    _write_trajectory(tmp_path, prior_value=100.0,
                      current_value=101.0)
    cand = tmp_path / "candidate.json"
    cand.write_text(json.dumps({"metric": "m", "value": None}))
    assert bd.main(["--repo", str(tmp_path),
                    "--candidate", str(cand)]) == 0
    assert "nothing to diff" in capsys.readouterr().err


def test_bench_diff_empty_trajectory_is_no_baseline(tmp_path, capsys):
    """No BENCH_r*.json at all (the repo's own state: the old chip
    records are gone) means no baseline, not a failure."""
    bd = _load_bench_diff()
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(
        {"metric": "m", "value": 50.0}))
    assert bd.main(["--repo", str(tmp_path)]) == 0
    assert "no baseline" in capsys.readouterr().err
    assert bd.main(["--repo", str(REPO_ROOT)]) == 0
