"""Host-level fault domains (ISSUE 17): the multi-host plane survives
losing a WHOLE host mid-stream.

The acceptance shape (docs/ROBUSTNESS.md "Host fault domains"): a
seeded HostLoss fires at a warm supervised seam while a multi-host
plane (parallel/plane.py, simulated fault domains carved out of the 8
virtual CPU devices) is streaming — the supervisor must classify it as
``host_loss``, quarantine the whole domain in ONE host-granular
reshrink (2x4 -> 1x4, not a device-by-device crawl), replay the lost
host's journaled in-flight intents (recovery/journal.py ``reclaim``),
finish byte-identical to the unfailed control, and re-promote back to
full host width once the adversary releases.  Satellites ride along:
the ``HostFaultPlan`` window/flap/membership semantics, the
``ProbeTimeout`` terminal probe error, the width-1 reshrink floor, the
``host-chaos`` bench workload, the ``host_chaos`` bench_diff category,
and the audit-registry entries.  The flap/partition torture sweeps run
@slow; tools/test_full.sh adds the real-process SIGKILL gate
(tools/host_chaos_demo.py --kill-one).
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

from ceph_tpu.chaos.hosts import (
    HostFault,
    HostFaultPlan,
    HostFlap,
    HostLoss,
    HostPartition,
    InjectedHostLoss,
    InjectedHostPartition,
    arm_host_plan,
    host_chaos_selftest,
    host_faults,
)
from ceph_tpu.ops import fallback
from ceph_tpu.ops.supervisor import (
    DispatchSupervisor,
    classify_dispatch_error,
    set_global_supervisor,
)
from ceph_tpu.utils.errors import ProbeTimeout, TransientBackendError
from ceph_tpu.utils.retry import FakeClock, RetryPolicy, probe_call

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ----------------------------------------------------------------------
# fixtures: isolated supervisor + policy + recorder + plane per test

@pytest.fixture
def sup():
    pol = fallback.FallbackPolicy(force=None)
    prev_pol = fallback.set_global_policy(pol)
    s = DispatchSupervisor(clock=FakeClock(), self_verify=True,
                           deadline_s=0.05, promote_after=2,
                           probe_every=1)
    prev = set_global_supervisor(s)
    from ceph_tpu.telemetry import recorder
    rec = recorder.FlightRecorder()
    prev_rec = recorder.set_global_flight_recorder(rec)
    try:
        yield s
    finally:
        set_global_supervisor(prev)
        fallback.set_global_policy(prev_pol)
        recorder.set_global_flight_recorder(prev_rec)
        arm_host_plan(None)


@pytest.fixture
def no_plane():
    from ceph_tpu.parallel import plane
    prev = plane.set_data_plane(None)
    yield
    plane.set_data_plane(prev)


@pytest.fixture
def two_host_plane(no_plane):
    """A 2-domain plane over the 8 virtual devices (conftest forces
    them), torn down with the previous plane restored by no_plane."""
    from ceph_tpu.parallel import plane
    p = plane.activate(None, hosts=2)
    assert p is not None and p.hosts == 2
    yield p


def _mk_ec(k=4, m=2):
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    return ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van",
                     "k": str(k), "m": str(m)})


def _equal(a, b) -> bool:
    if isinstance(a, (tuple, list)):
        return all(_equal(x, y) for x, y in zip(a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


def _triggers() -> list:
    from ceph_tpu.telemetry import recorder
    return [d["trigger"] for d in
            recorder.global_flight_recorder().to_dict()["dumps"]]


def _serve_driver(B=4, C=1024):
    from ceph_tpu.codes.engine import serve_dispatch_call
    ec = _mk_ec()
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (B, ec.get_data_chunk_count(), C),
                        np.uint8)

    def call():
        return np.asarray(serve_dispatch_call(ec, "encode")(data))

    return call


# ----------------------------------------------------------------------
# HostFaultPlan semantics

def test_host_fault_window_semantics():
    plan = HostFaultPlan(
        [HostLoss(1, seam="s", at=2, calls=2)], seed=0)
    assert plan.poll("s", hosts=2) is None            # poll 1
    assert plan.poll("other", hosts=2) is None        # per-seam idx
    assert plan.poll("s", hosts=2).kind == "host_loss"   # poll 2
    assert plan.poll("s", hosts=2).kind == "host_loss"   # poll 3
    assert plan.poll("s", hosts=2) is None            # window closed
    assert len(plan.fired) == 2


def test_host_flap_windows():
    # down for 2 polls, up for 1, two cycles starting at poll 2:
    # down at polls 2,3 and 5,6 — up everywhere else, forever after
    plan = HostFaultPlan(
        [HostFlap(1, seam="s", at=2, calls=2, up_calls=1, cycles=2)],
        seed=0)
    got = [plan.poll("s", hosts=2) is not None for _ in range(8)]
    assert got == [False, True, True, False, True, True, False, False]


def test_plane_membership_gates_firing():
    """A fault only fires while its host is still part of the plane:
    after the reshrink evicts host 1, the plan goes quiet — but the
    window still ADVANCES (flap timelines stay aligned)."""
    plan = HostFaultPlan(
        [HostLoss(1, seam="s", at=1, calls=3)], seed=0)
    assert plan.poll("s", hosts=1) is None   # evicted: quiet (poll 1)
    assert plan.poll("s", hosts=0) is None   # numpy floor (poll 2)
    assert plan.poll("s", hosts=2).host == 1  # member again (poll 3)
    assert plan.poll("s", hosts=2) is None   # poll 4: window closed
    assert plan.down_hosts(2) == ()


def test_pending_persistent_and_clear():
    plan = HostFaultPlan([HostLoss(1, seam="s", calls=None)], seed=0)
    # plane-independent ON PURPOSE: the health probe must keep failing
    # while the adversary holds the host, even after the reshrink
    assert plan.pending_persistent()
    for _ in range(3):
        assert plan.poll("s", hosts=2) is not None
    assert plan.down_hosts(2) == (1,)
    plan.clear()
    assert plan.poll("s", hosts=2) is None
    assert not plan.pending_persistent()
    assert plan.summary()["cleared"] is True
    finite = HostFaultPlan([HostLoss(1, seam="s", calls=2)], seed=0)
    assert not finite.pending_persistent()


def test_host_fault_validation():
    with pytest.raises(ValueError):
        HostFault("nope")
    with pytest.raises(ValueError):
        HostFault("host_loss", host=-1)
    with pytest.raises(ValueError):
        HostFault("host_loss", at=0)
    with pytest.raises(ValueError):
        HostFault("host_loss", calls=0)
    with pytest.raises(ValueError):
        # a flap window needs finite down-calls
        HostFault("host_flap", up_calls=2, calls=None)


def test_host_classifier():
    assert classify_dispatch_error(InjectedHostLoss("h")) == "host_loss"
    assert classify_dispatch_error(
        InjectedHostPartition("h")) == "host_loss"
    # the real-fleet message shapes (jax.distributed / slice health)
    assert classify_dispatch_error(RuntimeError(
        "UNAVAILABLE: host unreachable")) == "host_loss"
    assert classify_dispatch_error(RuntimeError(
        "coordination service: peer down")) == "host_loss"
    # a wedged PROBE is the hang class, not a host loss: the prober
    # names the target, the classifier must not guess domains
    assert classify_dispatch_error(
        ProbeTimeout("backend", 1.0)) == "backend_loss"
    assert classify_dispatch_error(RuntimeError("plain bug")) is None


# ----------------------------------------------------------------------
# probe_call / ProbeTimeout (satellite: the terminal probe error)

def test_probe_call_terminal_on_exhaustion():
    clock = FakeClock()
    calls = {"n": 0}

    def wedged():
        calls["n"] += 1
        raise TransientBackendError("no answer")

    with pytest.raises(ProbeTimeout) as ei:
        probe_call(wedged, target="host1", deadline=1.0,
                   policy=RetryPolicy(attempts=3, base_delay=0.01),
                   clock=clock)
    # terminal by design: RetryExhausted is swallowed, the probe
    # report carries the target + budget + what actually happened
    assert ei.value.target == "host1"
    assert ei.value.deadline == 1.0
    assert isinstance(ei.value.last, TransientBackendError)
    assert calls["n"] == 3


def test_probe_call_slow_answer_is_a_timeout():
    clock = FakeClock()

    def slow():
        clock.sleep(2.5)          # answers, but after the budget
        return "late"

    with pytest.raises(ProbeTimeout) as ei:
        probe_call(slow, target="host1", deadline=1.0, clock=clock)
    assert ei.value.deadline_expired
    assert probe_call(lambda: "ok", target="host1", deadline=1.0,
                      clock=clock) == "ok"


# ----------------------------------------------------------------------
# journal reclaim (satellite: in-flight survival)

def test_journal_reclaim_returns_redo_and_fences():
    from ceph_tpu.chaos.store import ShardStore
    from ceph_tpu.recovery.journal import IntentJournal, payload_digest
    j = IntentJournal()
    store = ShardStore({0: b"x" * 64})
    full, torn = b"a" * 64, b"b" * 64
    # op 0: every write landed -> completed, NOT re-dispatched
    j.begin(0, 0, epoch=5, payloads={1: full}, targets={1: 1})
    store.write(1, full)
    # op 1: the lost host died mid-write (torn prefix) -> rolled back,
    # the stale bytes deleted, the record RETURNED for re-dispatch
    j.begin(1, 0, epoch=5, payloads={2: torn}, targets={2: 2})
    store.write(2, torn[:10])
    # op 2: begun AFTER the loss was detected (survivor epoch) ->
    # fenced out of the reclaim, stays pending
    j.begin(2, 0, epoch=7, payloads={3: full}, targets={3: 3})
    stats, redo = j.reclaim([store], fence_epoch=7)
    assert stats.replayed == 2
    assert stats.completed == 1 and stats.rolled_back == 1
    assert [r.op_id for r in redo] == [1]
    assert redo[0].payloads == {2: payload_digest(torn)}
    assert 2 not in store.shards          # stale prefix rolled back
    assert bytes(store.shards[1]) == full  # completed write kept
    assert [r.op_id for r in j.pending()] == [2]


# ----------------------------------------------------------------------
# the acceptance arc: HostLoss mid-stream on the multi-host plane

def test_host_loss_reshrinks_host_granular_and_repromotes(
        sup, two_host_plane):
    """The tentpole: a persistent HostLoss at a warm seam — ONE
    host-granular reshrink (2x4 -> 1x4: the survivor keeps every one
    of its devices), in-flight reclaim hook fired, byte-identical
    completion, held down until the adversary releases, then
    re-promotion restores the full host topology."""
    from ceph_tpu.parallel import plane as planemod
    data = np.arange(128, dtype=np.uint8).reshape(8, 16)

    def body(x):
        return x ^ np.uint8(0x3C)

    want = body(data)
    reclaims = []
    sup.set_inflight_reclaim(lambda seam: reclaims.append(seam) or 1)
    with host_faults(HostFaultPlan(
            [HostLoss(1, seam="stream.batch", at=2, calls=None)],
            seed=3)) as plan:
        for _ in range(4):
            got = sup.dispatch("stream.batch", body, (data,),
                               host_fn=body, rebuild=lambda: body)
            assert np.array_equal(np.asarray(got), want)
        st = sup.stats()
        assert st["host_quarantines"] == 1     # ONE reshrink, 2 -> 1
        assert st["quarantines"] == 0          # not a device crawl
        assert st["journal_redispatches"] >= 1
        assert reclaims == ["stream.batch"]
        p = planemod.data_plane()
        assert p is not None and p.hosts == 1
        assert p.devices_per_host == two_host_plane.devices_per_host
        assert "host_quarantined" in _triggers()
        # the adversary still holds the host: clean-probe ticks must
        # NOT re-admit the domain (pending_persistent fences it)
        for _ in range(sup.promote_after + 2):
            sup.tick()
        assert sup.stats()["host_repromotions"] == 0
        plan.clear()
        for _ in range(sup.promote_after + 2):
            sup.tick()
    st = sup.stats()
    assert st["host_repromotions"] == 1
    assert not sup.demoted
    p = planemod.data_plane()
    assert p is not None and p.hosts == 2      # full width restored
    assert "repromoted" in _triggers()
    got = sup.dispatch("stream.batch", body, (data,),
                       host_fn=body, rebuild=lambda: body)
    assert np.array_equal(np.asarray(got), want)


def test_repair_batched_survives_midstream_host_loss(
        sup, two_host_plane):
    """Acceptance 1/2: HostLoss mid-``repair_batched`` — the second
    fused pattern batch lands on the dead host; zero data loss,
    byte-identical heal, host-granular reshrink, re-promotion."""
    from ceph_tpu.chaos import ShardErasure, inject
    from ceph_tpu.codes.stripe import HashInfo, StripeInfo
    from ceph_tpu.codes.stripe import encode as stripe_encode
    from ceph_tpu.recovery.orchestrator import healed
    from ceph_tpu.scrub import repair_batched
    ec = _mk_ec()
    n = ec.get_chunk_count()
    k = ec.get_data_chunk_count()
    sinfo = StripeInfo(k, k * 512)
    rng = np.random.default_rng(17)
    originals, stores, hinfos = [], [], []
    for i in range(4):
        obj = rng.integers(0, 256, k * 512, np.uint8).tobytes()
        shards = stripe_encode(sinfo, ec, obj)
        hinfo = HashInfo(n)
        hinfo.append(0, shards)
        store, _ = inject(shards, [ShardErasure(shards=[i % 2])],
                          seed=200 + i, chunk_size=sinfo.chunk_size)
        originals.append(shards)
        stores.append(store)
        hinfos.append(hinfo)
    with host_faults(HostFaultPlan(
            [HostLoss(1, seam="engine.fused_repair", at=2,
                      calls=None)], seed=19)) as plan:
        rep = repair_batched(sinfo, ec, stores, hinfos, device=True)
        plan.clear()
    assert rep.pattern_batches == 2
    assert healed(stores, originals)           # zero data loss
    for st_, orig in zip(stores, originals):
        for s, buf in orig.items():
            assert bytes(st_.shards[s]) == bytes(buf)
    st = sup.stats()
    assert st["host_quarantines"] >= 1
    assert "host_quarantined" in _triggers()
    for _ in range(sup.promote_after + 2):
        sup.tick()
    assert sup.stats()["host_repromotions"] >= 1
    assert not sup.demoted


def test_serving_stream_survives_midstream_host_loss(
        sup, two_host_plane):
    """Acceptance 2/2: HostLoss mid-serving-stream — every response in
    the stream stays byte-identical to the unfailed control while the
    plane reshrinks under it, and the stream never sees an error."""
    call = _serve_driver()
    control = call()
    with host_faults(HostFaultPlan(
            [HostLoss(1, seam="engine.serve-encode", at=3,
                      calls=None)], seed=23)) as plan:
        for _ in range(6):
            assert _equal(call(), control)
        st = sup.stats()
        assert st["host_quarantines"] >= 1
        assert "host_quarantined" in _triggers()
        plan.clear()
        for _ in range(sup.promote_after + 2):
            sup.tick()
    assert sup.stats()["host_repromotions"] >= 1
    assert not sup.demoted
    assert _equal(call(), control)


def test_width1_host_loss_completes_on_floor(sup, no_plane):
    """Satellite 3: the reshrink floor — a host loss with NO plane at
    all (the process is its single fault domain) cannot reshrink, so
    the ladder demotes to the numpy ground-truth twin and the dispatch
    STILL completes byte-identically."""
    data = np.arange(64, dtype=np.uint8)

    def body(x):
        return x ^ np.uint8(0x81)

    with host_faults(HostFaultPlan(
            [HostLoss(0, seam="floor.batch", at=1, calls=1)],
            seed=29)):
        out = sup.dispatch("floor.batch", body, (data,), host_fn=body,
                           rebuild=lambda: body)
    assert np.array_equal(out, body(data))
    st = sup.stats()
    assert st["host_quarantines"] == 0     # nothing to reshrink
    assert st["demotions"] >= 1 and st["host_completions"] >= 1
    for _ in range(sup.promote_after + 2):
        sup.tick()
    assert not sup.demoted


def test_host_partition_quarantines_and_fences(sup, two_host_plane):
    """A partitioned host is alive (it may still emit stale writes) —
    same reshrink arc, but the injected error type is distinct so the
    journal re-dispatch path can epoch-fence its output."""
    data = np.arange(32, dtype=np.uint8)

    def body(x):
        return x ^ np.uint8(0x07)

    plan = HostFaultPlan(
        [HostPartition(1, seam="part.batch", at=1, calls=None)],
        seed=31)
    assert plan.active("part.batch", hosts=2).kind == "host_partition"
    with host_faults(plan):
        out = sup.dispatch("part.batch", body, (data,), host_fn=body,
                           rebuild=lambda: body)
        assert np.array_equal(out, body(data))
        st = sup.stats()
        assert st["host_quarantines"] == 1
        # still fenced while the partition stands
        for _ in range(sup.promote_after + 2):
            sup.tick()
        assert sup.stats()["host_repromotions"] == 0
        plan.clear()
        for _ in range(sup.promote_after + 2):
            sup.tick()
    assert sup.stats()["host_repromotions"] == 1


# ----------------------------------------------------------------------
# scenario runner + spec wiring

def test_scenario_spec_roundtrips_host_loss():
    from dataclasses import replace

    from ceph_tpu.scenario.spec import default_scenario
    spec = default_scenario()
    spec = replace(spec, chaos=replace(
        spec.chaos, host_loss="host_flap", host_loss_host=0,
        host_loss_hosts=4, host_loss_at=3, host_loss_calls=None))
    again = type(spec).from_json(spec.to_json())
    assert again == spec
    assert again.chaos.host_loss == "host_flap"
    assert again.chaos.host_loss_hosts == 4
    assert again.chaos.host_loss_calls is None


def test_scenario_runner_host_loss_section(sup, no_plane):
    """The production-day runner arms the plan, activates the
    multi-host plane, survives the mid-stream loss and reports the
    ``host_plane`` section (docs/SCENARIOS.md)."""
    from dataclasses import replace

    from ceph_tpu.scenario import default_scenario, run_scenario
    from ceph_tpu.serve.loadgen import throughput_service_model
    base = default_scenario(seed=42, n_requests=10, stripe_size=1024,
                            damaged_objects=1, erasures=1,
                            storm_events=1)
    spec = replace(base, chaos=replace(
        base.chaos, host_loss="host_loss", host_loss_at=2,
        host_loss_calls=None))
    run = run_scenario(spec, clock=FakeClock(), executor="device",
                       service_model=throughput_service_model())
    rep = run.report
    assert rep.gates["converged"] and rep.gates["healed"]
    assert rep.gates["verified_requests"]
    hp = rep.host_plane
    assert hp is not None
    assert hp["plan"]["fired"] >= 1
    assert hp["counters"]["host_quarantines"] >= 1
    assert hp["counters"]["host_repromotions"] >= 1
    assert hp["topology_armed"] == {"hosts": 2, "devices_per_host": 4}
    assert hp["topology_at_end"] == hp["topology_armed"]
    assert not hp["demoted_at_end"]
    assert rep.to_dict()["host_plane"]["fault"]["kind"] == "host_loss"


# ----------------------------------------------------------------------
# bench + bench_diff + audit satellites

def test_bench_host_chaos_workload_host(sup, no_plane):
    from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench
    bench = ErasureCodeBench()
    bench.setup(["-p", "jerasure", "-P", "technique=reed_sol_van",
                 "-P", "k=4", "-P", "m=2", "-s", "4096",
                 "--workload", "host-chaos", "--device", "host",
                 "--batch", "2", "--iterations", "1", "-e", "1"])
    res = bench.run()
    assert res["workload"] == "host-chaos"
    assert res["verified"] is True
    assert res["faults_fired"] >= 1
    # host executor: one fault domain, so the loss demotes to the
    # ground-truth twin instead of reshrinking (the width-1 floor)
    assert res["hosts"] == 1
    assert res["supervisor"]["demotions"] >= 1
    assert res["supervisor"]["host_completions"] >= 1
    assert res["demoted_at_end"] is False


def _load_bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff_host", REPO_ROOT / "tools" / "bench_diff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_flags_host_chaos_regression(tmp_path, capsys):
    """Red fixture: a 60% survival-throughput drop trips the sentinel
    under the host_chaos category's own floor; green passes."""
    bd = _load_bench_diff()
    prior = {"metric": "m", "value": 100.0, "git_sha": "aaa",
             "timestamp": "2026-01-01T00:00:00+00:00",
             "host_chaos_rows": {"rs": {"gbps": 1.0}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": prior}))
    cur = {"metric": "m", "value": 100.0, "git_sha": "bbb",
           "timestamp": "2026-02-01T00:00:00+00:00",
           "host_chaos_rows": {"rs": {"gbps": 0.4}}}
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == ["host_chaos:rs"]
    cur["host_chaos_rows"]["rs"]["gbps"] = 0.8
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0


def test_host_plane_audit_entries_registered():
    from ceph_tpu.analysis.entrypoints import registry
    names = {e.name: e for e in registry()}
    assert names["chaos.host_plane"].kind == "host"
    assert names["chaos.host_plane"].family == "chaos"
    assert names["engine.fused_repair_host_sharded"].kind == "jit"


def test_host_chaos_selftest_green(no_plane):
    st = host_chaos_selftest()
    # conftest forces 8 virtual devices, so the multi-host arc runs
    assert st["multi_host"] is True
    assert st["host_quarantines"] >= 1
    assert st["host_repromotions"] >= 1
    assert st["journal_redispatches"] >= 1
    assert not st["demoted"]
    assert st["plan"]["fired"] >= 1


def test_plane_degrade_routes_through_shared_shape(no_plane):
    """Satellite 1: plane activation-time degrade and the supervisor's
    quarantine paths emit the SAME ``engine_mesh_degraded`` shape —
    one flight-ring note kind for every plane-narrowing event."""
    from ceph_tpu.parallel import plane as planemod
    from ceph_tpu.telemetry import recorder
    rec = recorder.FlightRecorder()
    prev_rec = recorder.set_global_flight_recorder(rec)
    try:
        planemod._degrade("unit-test narrowing")
        kinds = [e["kind"] for e in rec.to_dict()["entries"]]
        assert "engine_mesh_degraded" in kinds
        entry = [e for e in rec.to_dict()["entries"]
                 if e["kind"] == "engine_mesh_degraded"][-1]
        assert entry["reason"] == "unit-test narrowing"
        assert entry["seam"] == "parallel.plane.activate"
    finally:
        recorder.set_global_flight_recorder(prev_rec)


# ----------------------------------------------------------------------
# torture sweeps (@slow: the full suite / tools/test_full.sh)

@pytest.mark.slow
def test_host_flap_torture(sup, two_host_plane):
    """A flapping host (down 2 / up 2, three cycles) across a 24-call
    stream: every completion byte-identical, multiple quarantine +
    re-promotion round trips, clean exit at full width."""
    from ceph_tpu.parallel import plane as planemod
    data = np.arange(256, dtype=np.uint8).reshape(16, 16)

    def body(x):
        return x ^ np.uint8(0x42)

    want = body(data)
    with host_faults(HostFaultPlan(
            [HostFlap(1, seam="flap.batch", at=2, calls=2, up_calls=2,
                      cycles=3)], seed=37)) as plan:
        for _ in range(24):
            got = sup.dispatch("flap.batch", body, (data,),
                               host_fn=body, rebuild=lambda: body)
            assert np.array_equal(np.asarray(got), want)
        plan.clear()
        for _ in range(sup.promote_after + 2):
            sup.tick()
    st = sup.stats()
    assert st["host_quarantines"] >= 2     # each down window evicts
    assert st["host_repromotions"] >= 2    # each up window re-admits
    assert not sup.demoted
    p = planemod.data_plane()
    assert p is not None and p.hosts == 2


@pytest.mark.slow
def test_host_partition_torture_scenario(sup, no_plane):
    """The production day under a host partition (executor=device):
    converged + healed + verified with the reshrink visible in the
    host_plane report section."""
    from dataclasses import replace

    from ceph_tpu.scenario import default_scenario, run_scenario
    from ceph_tpu.serve.loadgen import throughput_service_model
    base = default_scenario(seed=43, n_requests=12, stripe_size=1024,
                            damaged_objects=2, erasures=1,
                            storm_events=2)
    spec = replace(base, chaos=replace(
        base.chaos, host_loss="host_partition", host_loss_at=2,
        host_loss_calls=None))
    run = run_scenario(spec, clock=FakeClock(), executor="device",
                       service_model=throughput_service_model())
    rep = run.report
    assert rep.gates["converged"] and rep.gates["healed"]
    assert rep.gates["verified_requests"]
    hp = rep.host_plane
    assert hp["plan"]["fired_kinds"] == ["host_partition"]
    assert hp["counters"]["host_quarantines"] >= 1
    assert hp["topology_at_end"] == hp["topology_armed"]
