"""Multi-tenant weeks tier-1 slice (ISSUE 19, docs/SCENARIOS.md
"Multi-tenant weeks").

The acceptance axes:

- tenant-aware ScenarioSpec JSON round trip; a tenantless spec's dict
  stays byte-identical to before (no new keys on the legacy shape).
- Per-tenant mClock: limit is THE isolation contract (the only
  denial), reservation/weight tenants are never door-denied, and
  ``tenant_hold`` is the deterministic shed-retry horizon.
- Replay determinism: same seed ⇒ byte-identical report JSON, and
  the discrete-event clock ≡ the stepped clock (fast-forward skips
  only idle time — identical per-request results, identical batch
  composition via dispatch_crc, identical report).
- The staged-disaster machine: every stage arms, fires, heals
  byte-identically (zero data loss) with its flight-recorder dump.
- The pinned isolation gate: victims within 1.5x p99 / 2x miss of
  their isolated baselines arbiter-on, and the SAME gate fails on
  the arbiter-off control arm.
- The satellites: rejects counted as per-tenant misses, per-tenant
  trace sampling with counted drops, MapChurn at 100k-OSD width
  (incremental ≡ rebuilt), histogram exemplar capacity under a
  1e6-sample merge, the ``tenant_isolation`` bench_diff category.
"""

import importlib.util
import json
import pathlib
from types import SimpleNamespace

import pytest

from ceph_tpu.chaos.adversaries import MapChurn
from ceph_tpu.crush.builder import CrushBuilder
from ceph_tpu.crush.incremental import catch_up
from ceph_tpu.crush.osdmap import OSDMap
from ceph_tpu.scenario import (
    DISASTER_KINDS,
    MClockArbiter,
    ScenarioSpec,
    default_scenario,
    isolated_baseline,
    isolation_gate,
    run_tenant_week,
    tenant_week_scenario,
    week_selftest,
)
from ceph_tpu.serve.sla import SlaRecorder
from ceph_tpu.telemetry.histogram import LatencyHistogram
from ceph_tpu.telemetry.tracing import TraceCollector
from ceph_tpu.utils.retry import FakeClock

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def tiny_spec(**overrides):
    """The pinned tiny week: small enough for the tier-1 loop, hot
    enough (burst 80x into partial-occupancy buckets) that the
    isolation gate separates the arbiter arms."""
    kw = dict(seed=17, days=2, day_s=6.0,
              peak_rates=(40.0, 30.0, 20.0), burst_factor=80.0)
    kw.update(overrides)
    return tenant_week_scenario(**kw)


@pytest.fixture(scope="module")
def week_run():
    return run_tenant_week(tiny_spec())


# ----------------------------------------------------------------------
# spec

def test_tenant_spec_json_roundtrip():
    spec = tiny_spec()
    clone = ScenarioSpec.from_json(spec.to_json())
    assert clone == spec
    assert clone.to_json() == spec.to_json()
    assert tuple(t.name for t in clone.tenants) == (
        "alpha", "bravo", "noisy")
    assert tuple(s.kind for s in clone.disasters.stages) == (
        "rack_loss", "backend_loss", "host_loss", "tenant_burst")
    assert all(s.kind in DISASTER_KINDS
               for s in clone.disasters.stages)


def test_tenantless_spec_dict_unchanged():
    # byte-compat gate: the legacy single-stream spec must not grow
    # tenant keys (every pre-week golden/replay artifact depends on it)
    d = default_scenario(seed=7, n_requests=16).to_dict()
    assert "tenants" not in d and "disasters" not in d


def test_tenant_week_factory_shape():
    spec = tiny_spec()
    limits = {t.name: t.limit for t in spec.tenants}
    assert limits["alpha"] == 0.0 and limits["bravo"] == 0.0
    assert limits["noisy"] > 0.0      # the noisy neighbor is capped
    # stage times are week FRACTIONS: every stage lands inside the
    # compressed week whatever ``days`` is
    week_s = spec.traffic.diurnal_period_s * 2
    for st in spec.disasters.stages:
        assert 0.0 < st.at_s < week_s


# ----------------------------------------------------------------------
# per-tenant mClock

def test_tenant_limit_is_the_only_denial():
    clock = FakeClock()
    arb = MClockArbiter(clock=clock, enabled=True)
    arb.register_tenant("alpha", reservation=5.0, weight=4.0,
                        limit=0.0)
    arb.register_tenant("noisy", reservation=1.0, weight=1.0,
                        limit=2.0)
    # limit 0 = uncapped: alpha is NEVER door-denied, however fast
    assert all(arb.admit_tenant("alpha") for _ in range(200))
    # noisy is clamped at 2 ops/s: a burst gets denied at the door
    granted = sum(arb.admit_tenant("noisy") for _ in range(50))
    assert 0 < granted < 50
    hold = arb.tenant_hold("noisy")
    assert hold > 0.0                 # deterministic retry horizon
    clock.sleep(hold)
    assert arb.admit_tenant("noisy")  # limit tag due again
    # unregistered tenants and the disabled control always pass
    assert arb.admit_tenant("ghost")
    off = MClockArbiter(clock=FakeClock(), enabled=False)
    off.register_tenant("noisy", limit=2.0)
    assert all(off.admit_tenant("noisy") for _ in range(50))
    assert off.tenant_hold("noisy") == 0.0


# ----------------------------------------------------------------------
# replay + clock modes

def test_week_replay_byte_identical(week_run):
    again = run_tenant_week(tiny_spec())
    assert again.report.to_json() == week_run.report.to_json()


def test_discrete_event_equals_stepped_clock(week_run):
    """Satellite 4: fast-forward must skip ONLY idle time — the
    stepped clock (no jumps) produces the identical report: same
    per-request results, same batch composition (dispatch_crc), same
    per-tenant scorecards, byte-identical JSON."""
    stepped = run_tenant_week(tiny_spec(), clock_mode="step")
    rep, srep = week_run.report, stepped.report
    assert srep.gates["dispatch_crc"] == rep.gates["dispatch_crc"]
    assert srep.tenants == rep.tenants
    assert srep.to_json() == rep.to_json()


# ----------------------------------------------------------------------
# the staged-disaster machine

def test_disaster_stages_fire_and_heal(week_run):
    rep = week_run.report
    assert rep.gates["converged"] and rep.gates["healed"]
    assert rep.gates["verified_requests"]    # zero data loss
    assert [d["kind"] for d in rep.disasters] == [
        "rack_loss", "backend_loss", "host_loss", "tenant_burst"]
    for d in rep.disasters:
        assert d["fired_at"] is not None
        assert d["healed"] and d["converged"]
        assert d["healed_at"] > d["fired_at"]
        assert d["dumped"]                   # flight dump per stage
    rack = rep.disasters[0]
    # a whole rack down means CRUSH_ITEM_NONE slots: recovery runs
    # degraded with fence-deferred write-backs until the heal revives
    assert rack["recovery_rounds"] > 0
    assert rack["fence_deferrals"] > 0
    assert rack["osds_downed"] > 0


def test_week_scale_and_selftest(week_run):
    g = week_run.report.gates
    assert g["requests_offered"] > 1000
    assert g["dispatched"] > 0
    # 10x diurnal swing: the factory's floor fraction
    assert tiny_spec().traffic.diurnal_min_frac == pytest.approx(0.1)
    week_selftest()


# ----------------------------------------------------------------------
# the isolation gate (slow-ish: three extra runs on the tiny week)

def test_isolation_gate_on_passes_off_fails(week_run):
    spec = tiny_spec()
    base = {n: isolated_baseline(spec, n) for n in ("alpha", "bravo")}
    on = isolation_gate(week_run.report, base)
    assert on["ok"], on
    for v in on["victims"].values():
        assert v["p99_ms"] <= 1.5 * v["baseline_p99_ms"]
    off_rep = run_tenant_week(spec, enable_arbiter=False).report
    off = isolation_gate(off_rep, base)
    assert not off["ok"], off
    # the control still converges + heals: the arbiter buys latency
    # isolation, not correctness
    assert off_rep.gates["converged"] and off_rep.gates["healed"]


# ----------------------------------------------------------------------
# satellite: rejects counted as per-tenant misses

def test_rejects_are_per_tenant_misses(week_run):
    tens = week_run.report.tenants
    noisy = tens["noisy"]
    assert noisy["rejected"].get("qos_limit", 0) > 0
    for t in tens.values():
        rej = sum(t["rejected"].values())
        assert t["requests"] == t["served"] + rej


def test_record_reject_folds_into_scorecard():
    rec = SlaRecorder()
    req = SimpleNamespace(op="encode", tenant="alpha")
    rec.record_reject(req, "qos_limit")
    rec.record_reject(req, "qos_limit")
    rec.record_reject(SimpleNamespace(op="decode", tenant="bravo"),
                      "capacity")
    rep = rec.report(elapsed=1.0)
    assert rep["rejected_misses"] == 3
    assert rec.rejects == {
        "encode": {"qos_limit": 2}, "decode": {"capacity": 1}}
    assert rep["deadline_miss_rate"] == 1.0
    t = rep["tenants"]
    assert t["alpha"]["rejected"] == {"qos_limit": 2}
    assert t["alpha"]["requests"] == 2 and t["alpha"]["served"] == 0
    assert t["alpha"]["deadline_miss_rate"] == 1.0
    assert t["bravo"]["rejected"] == {"capacity": 1}


# ----------------------------------------------------------------------
# satellite: per-tenant trace sampling + bounded memory

def test_tracing_per_tenant_sampling():
    col = TraceCollector(clock=FakeClock(), seed=3)
    col.set_tenant_sample({"alpha": 1.0, "noisy": 0.0})
    assert all(col.sampled(n, "alpha") for n in range(64))
    assert not any(col.sampled(n, "noisy") for n in range(64))
    # unlisted tenants fall back to the collector-wide rate
    assert all(col.sampled(n, "ghost") for n in range(8))


def test_tracing_drops_counted_per_tenant():
    col = TraceCollector(clock=FakeClock(), seed=3, max_traces=2)
    assert col.begin("client", 0, tenant="alpha") is not None
    assert col.begin("client", 1, tenant="noisy") is not None
    assert col.begin("client", 2, tenant="noisy") is None
    assert col.begin("client", 3, tenant="noisy") is None
    assert col.begin("client", 4) is None      # untenanted bills ""
    assert col.dropped == 3
    assert col.dropped_by == {"noisy": 2, "": 1}
    d = col.to_dict()
    assert d["dropped_by"] == {"noisy": 2, "": 1}
    # byte-compat: a collector that never saw tenants dumps the
    # legacy shape (no new keys)
    legacy = TraceCollector(clock=FakeClock(), seed=3).to_dict()
    assert "tenant_sample" not in legacy
    assert "dropped_by" not in legacy


# ----------------------------------------------------------------------
# satellite: MapChurn at 100k-OSD width

def _wide_map(max_osd):
    b = CrushBuilder()
    b.build_two_level(4, 2)
    return OSDMap(crush=b.map, max_osd=max_osd)


def test_mapchurn_100k_incremental_equals_rebuilt():
    """Property test: 200 churn events against a 100k-OSD map via
    the seeded probe path, then a FRESH map caught up from the
    recorded incrementals must be byte-identical to the live one."""
    live = _wide_map(100_000)
    churn = MapChurn(seed=23, max_down=8, fire_every=1,
                     max_events=200)
    while len(churn.events) < 200:
        churn.step(live, "week")
    fresh = _wide_map(100_000)
    catch_up(fresh, churn.incrementals)
    assert fresh.epoch == live.epoch
    assert fresh.osd_up == live.osd_up
    assert fresh.osd_weight == live.osd_weight
    # 64 seeded probes against a fully-live 100k map never fall back
    # to the O(max_osd) scan
    assert churn.scan_fallbacks == 0
    assert len(churn.incrementals) == 200


def test_mapchurn_small_maps_keep_the_legacy_scan():
    # at or below scan_limit the exact legacy RNG schedule runs:
    # existing seeds replay byte-identically (no probe draws)
    live = _wide_map(64)
    churn = MapChurn(seed=5, max_down=4, fire_every=1, max_events=24)
    while len(churn.events) < 24:
        churn.step(live, "week")
    assert churn.scan_fallbacks == 0
    # forcing probe mode on the same small map still yields a valid
    # epoch-ordered incremental log (different RNG schedule, same
    # replay contract)
    live2 = _wide_map(64)
    forced = MapChurn(seed=5, max_down=4, fire_every=1,
                      max_events=24, scan_limit=1)
    while len(forced.events) < 24:
        forced.step(live2, "week")
    fresh = _wide_map(64)
    catch_up(fresh, forced.incrementals)
    assert fresh.osd_up == live2.osd_up
    assert fresh.osd_weight == live2.osd_weight


# ----------------------------------------------------------------------
# satellite: histogram exemplar capacity under merge

def test_exemplar_retention_matches_legacy_sort():
    # the O(1)-early-reject insertion must retain EXACTLY the set the
    # old sort-the-whole-list retention kept: top-capacity by value,
    # newest-first on ties
    h = LatencyHistogram(exemplars=16)
    shadow = []
    seq = 0
    for n in range(5000):
        v = float((n * 2654435761) % 97) / 97.0
        seq += 1
        h.record(v, exemplar=f"t{n}")
        shadow.append((v, seq, f"t{n}"))
        shadow.sort(key=lambda e: (-e[0], -e[1]))
        del shadow[16:]
    assert h._exemplars == shadow


@pytest.mark.slow
def test_exemplar_capacity_under_1e6_merge():
    """ISSUE 19 regression: 1e6 samples across 4 shards, every one
    carrying an exemplar id, must merge with the exemplar list
    bounded at capacity (the pre-fix path went quadratic and
    unbounded under merge)."""
    shards = []
    for s in range(4):
        h = LatencyHistogram(exemplars=32)
        for n in range(250_000):
            v = float((n * 2654435761 + s) % 1000003) / 1e6
            h.record(v, exemplar=f"s{s}:{n}")
        shards.append(h)
    total = LatencyHistogram(exemplars=32)
    for h in shards:
        total.merge(h)
    assert total.count == 1_000_000
    ex = total.exemplars()
    assert len(ex) == 32
    vals = [e["value"] for e in ex]
    assert vals == sorted(vals, reverse=True)


# ----------------------------------------------------------------------
# satellite: bench_diff tenant_isolation category

def _load_bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff_tenant", REPO_ROOT / "tools" / "bench_diff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_flags_tenant_isolation_regression(tmp_path,
                                                      capsys):
    """Red fixture: a 60% victim-throughput-under-SLO drop trips the
    sentinel under the tenant_isolation floor; green passes."""
    bd = _load_bench_diff()
    prior = {"metric": "m", "value": 100.0, "git_sha": "aaa",
             "timestamp": "2026-01-01T00:00:00+00:00",
             "tenant_week_rows": {"tenant_week_isolation": {
                 "victim_gbps_under_slo": 1.0}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "",
         "parsed": prior}))
    cur = {"metric": "m", "value": 100.0, "git_sha": "bbb",
           "timestamp": "2026-02-01T00:00:00+00:00",
           "tenant_week_rows": {"tenant_week_isolation": {
               "victim_gbps_under_slo": 0.4}}}
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == [
        "tenant_isolation:tenant_week_isolation"]
    cur["tenant_week_rows"]["tenant_week_isolation"][
        "victim_gbps_under_slo"] = 0.9
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0


# ----------------------------------------------------------------------
# audit registry

def test_week_audit_entry_registered():
    from ceph_tpu.analysis.entrypoints import registry
    names = {e.name: e for e in registry()}
    assert names["scenario.week"].kind == "host"
    assert names["scenario.week"].family == "scenario"
