"""The supervised dispatch plane (ISSUE 13): ops/supervisor.py +
chaos/dispatch.py.

- the torture matrix: fault kind x dispatch seam x engine tier,
  seeded, byte-identity vs the unfailed control + zero data loss
  pinned (tier-1 slice here; the full product runs @slow);
- health-probe re-promotion and quarantine-never-starves properties;
- mid-stream backend loss through repair_batched (the acceptance
  shape: warm seam, persistent fault, byte-identical heal, flight
  dump, logged re-promotion);
- DispatchFaultPlan window/replay semantics and the error classifier;
- the bench --workload device-chaos row and the bench_diff
  device_chaos category (red fixture).
"""

import importlib.util
import itertools
import json
import pathlib

import numpy as np
import pytest

from ceph_tpu.chaos.dispatch import (
    DispatchFault,
    DispatchFaultPlan,
    DispatchHang,
    InjectedBackendLoss,
    InjectedOom,
    arm_plan,
    dispatch_faults,
)
from ceph_tpu.codes.engine import fused_repair_call, serve_dispatch_call
from ceph_tpu.codes.registry import ErasureCodePluginRegistry
from ceph_tpu.ops import fallback
from ceph_tpu.ops.supervisor import (
    DispatchSupervisor,
    classify_dispatch_error,
    set_global_supervisor,
)
from ceph_tpu.utils.errors import RetryExhausted, TransientBackendError
from ceph_tpu.utils.retry import FakeClock

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ----------------------------------------------------------------------
# fixtures: isolated supervisor + policy + recorder per test

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
        arm_plan(None)


@pytest.fixture
def no_plane():
    from ceph_tpu.parallel import plane
    prev = plane.set_data_plane(None)
    yield
    plane.set_data_plane(prev)


def _mk_ec(k=4, m=2):
    return ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van",
                     "k": str(k), "m": str(m)})


def _equal(a, b) -> bool:
    if isinstance(a, (tuple, list)):
        return all(_equal(x, y) for x, y in zip(a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# seam drivers: each returns a zero-arg call producing host arrays

def _fused_driver(mesh=None, B=4, C=1024):
    ec = _mk_ec()
    n = ec.get_chunk_count()
    erased = (1,)
    avail = tuple(i for i in range(n) if i != 1)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (B, ec.get_data_chunk_count(), C),
                        np.uint8)
    parity = np.asarray(ec.encode_chunks_batch(data))
    surv = np.ascontiguousarray(
        np.concatenate([data, parity], axis=1)[:, np.array(avail), :])

    def call():
        out = fused_repair_call(ec, avail, erased, mesh=mesh)(surv)
        return tuple(np.asarray(o) for o in out)

    return call


def _serve_driver(B=4, C=1024):
    ec = _mk_ec()
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (B, ec.get_data_chunk_count(), C),
                        np.uint8)

    def call():
        return np.asarray(serve_dispatch_call(ec, "encode")(data))

    return call


def _ops_driver(B=4, C=1024):
    import jax.numpy as jnp

    from ceph_tpu.ops.pallas_gf import apply_matrix_best
    from ceph_tpu.ops.xla_ops import matrix_to_static
    ec = _mk_ec()
    ms = matrix_to_static(ec.matrix)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 256, (B, 4, C), np.uint8))

    def call():
        return np.asarray(apply_matrix_best(x, ms, 8))

    return call


def _bulk_driver(n_x=8):
    from ceph_tpu.crush import (CrushBuilder, step_chooseleaf_indep,
                                step_emit, step_take)
    from ceph_tpu.crush.bulk import CompiledCrushMap, bulk_do_rule
    b = CrushBuilder()
    root = b.build_two_level(4, 2)
    b.add_rule(0, [step_take(root), step_chooseleaf_indep(0, 1),
                   step_emit()])
    cm = CompiledCrushMap(b.map)
    xs = np.arange(n_x, dtype=np.int64)

    def call():
        out, cnt = bulk_do_rule(cm, 0, xs, 3)
        return np.asarray(out), np.asarray(cnt)

    return call


SEAMS = {
    "engine.fused_repair": _fused_driver,
    "engine.serve-encode": _serve_driver,
    "ops.apply_matrix": _ops_driver,
    "crush.bulk_rule": _bulk_driver,
}

KINDS = ("transient", "oom", "backend_loss", "hang", "corrupt")

# tier-1 slice of the torture matrix (the full product runs @slow)
TIER1_CASES = [
    ("engine.fused_repair", "transient"),
    ("engine.fused_repair", "oom"),
    ("engine.fused_repair", "backend_loss"),
    ("engine.fused_repair", "hang"),
    ("engine.fused_repair", "corrupt"),
    ("engine.serve-encode", "transient"),
    ("engine.serve-encode", "backend_loss"),
    ("ops.apply_matrix", "oom"),
    ("ops.apply_matrix", "backend_loss"),
    ("crush.bulk_rule", "backend_loss"),
    ("crush.bulk_rule", "oom"),
]

# the bulk seam opts out of self-verify (its device output carries
# need-host residue flags the exact-mapper twin resolves in one
# step), so corruption there is out of the matrix by design — the
# sanitizer mode (utils/debug.verification_enabled) covers that seam
EXCLUDED_CASES = {("crush.bulk_rule", "corrupt")}


def _torture_one(sup, seam, kind):
    """One torture cell: warm, arm, run-under-fault byte-identical,
    heal, re-promote, run-again byte-identical.  Zero data loss by
    construction: outputs ARE the data."""
    call = SEAMS[seam]()
    control = call()                     # warm + the unfailed bytes
    persistent = kind in ("backend_loss", "hang")
    faults = [DispatchFault(kind, seam=seam, at=1,
                            calls=(None if persistent else 1))]
    with dispatch_faults(faults, seed=5) as plan:
        out = call()
        assert _equal(out, control), f"{seam}/{kind}: bytes diverged"
        assert plan.fired, f"{seam}/{kind}: fault never fired"
        plan.clear()
    for _ in range(sup.promote_after + 1):
        sup.tick()
    assert not sup.demoted, f"{seam}/{kind}: still demoted after heal"
    assert fallback.global_policy().engine() == "xla"
    assert _equal(call(), control)
    st = sup.stats()
    if kind == "transient":
        assert st["retries"] >= 1
    elif kind == "oom":
        # splittable seams downshift; zero-dim/host seams demote
        assert st["rung_downshifts"] + st["demotions"] >= 1
    elif kind == "corrupt":
        assert st["verify_failures"] >= 1
    else:
        assert st["demotions"] >= 1
        assert st["repromotions"] >= 1


@pytest.mark.parametrize("seam,kind", TIER1_CASES)
def test_torture_matrix_tier1(sup, no_plane, seam, kind):
    _torture_one(sup, seam, kind)


@pytest.mark.slow
@pytest.mark.parametrize(
    "seam,kind",
    [c for c in itertools.product(SEAMS, KINDS)
     if c not in TIER1_CASES and c not in EXCLUDED_CASES])
def test_torture_matrix_full(sup, no_plane, seam, kind):
    _torture_one(sup, seam, kind)


# ----------------------------------------------------------------------
# the classification ladder, piece by piece

def test_transient_retries_without_demotion(sup, no_plane):
    call = _fused_driver()
    control = call()
    with dispatch_faults([DispatchFault("transient",
                                        seam="engine.fused_repair",
                                        at=1, calls=1)], seed=1):
        assert _equal(call(), control)
    st = sup.stats()
    assert st["retries"] == 1
    assert st["demotions"] == 0 and not sup.demoted


def test_oom_splits_batch_rung(sup, no_plane):
    call = _fused_driver(B=8)
    control = call()
    with dispatch_faults([DispatchFault("oom",
                                        seam="engine.fused_repair",
                                        at=1, calls=1)], seed=1):
        assert _equal(call(), control)
    st = sup.stats()
    assert st["rung_downshifts"] >= 1
    assert st["demotions"] == 0


def test_persistent_oom_never_starves(sup, no_plane):
    """A device that OOMs at EVERY rung splits down to batch 1, then
    demotes and completes on the numpy twin — the dispatch always
    completes, byte-identically."""
    call = _fused_driver(B=8)
    control = call()
    with dispatch_faults([DispatchFault("oom",
                                        seam="engine.fused_repair",
                                        at=1, calls=None)],
                         seed=1) as plan:
        assert _equal(call(), control)
        plan.clear()
    st = sup.stats()
    assert st["rung_downshifts"] >= 1
    assert st["demotions"] >= 1
    assert st["host_completions"] >= 1


def test_backend_loss_demotes_live_and_flight_dumps(sup, no_plane):
    from ceph_tpu.telemetry import recorder
    call = _fused_driver()
    control = call()
    pol = fallback.global_policy()
    assert pol.engine() == "xla"
    with dispatch_faults([DispatchFault("backend_loss",
                                        seam="engine.fused_repair",
                                        at=1, calls=None)],
                         seed=2) as plan:
        assert _equal(call(), control)
        assert pol.engine() == "numpy"      # LIVE demotion
        assert pol.demoted
        # every dispatch keeps completing on the ground-truth twin
        assert _equal(call(), control)
        plan.clear()
    triggers = [d["trigger"] for d in
                recorder.global_flight_recorder().to_dict()["dumps"]]
    assert "backend_demoted" in triggers


def test_hang_burns_deadline_then_demotes(sup, no_plane):
    call = _fused_driver()
    control = call()
    clock0 = sup.clock.now
    with dispatch_faults([DispatchFault("hang",
                                        seam="engine.fused_repair",
                                        at=1, calls=None)],
                         seed=3) as plan:
        assert _equal(call(), control)
        plan.clear()
    assert sup.clock.now > clock0           # the deadline was burned
    st = sup.stats()
    assert st["hangs"] >= 1 and st["demotions"] >= 1


def test_corrupt_output_caught_and_never_returned(sup, no_plane):
    from ceph_tpu.telemetry import recorder
    call = _fused_driver()
    control = call()
    with dispatch_faults([DispatchFault("corrupt",
                                        seam="engine.fused_repair",
                                        at=1, calls=1)], seed=4):
        out = call()
    assert _equal(out, control)             # never written back
    assert sup.stats()["verify_failures"] == 1
    triggers = [d["trigger"] for d in
                recorder.global_flight_recorder().to_dict()["dumps"]]
    assert "output_corruption" in triggers


def test_corrupt_propagates_without_self_verify(no_plane):
    """Self-verify OFF is the zero-overhead default: injected
    corruption then reaches the caller — which is exactly why the
    mode exists and why the test above pins the detection."""
    pol = fallback.FallbackPolicy(force=None)
    prev_pol = fallback.set_global_policy(pol)
    s = DispatchSupervisor(clock=FakeClock(), self_verify=False)
    prev = set_global_supervisor(s)
    try:
        call = _fused_driver()
        control = call()
        with dispatch_faults([DispatchFault(
                "corrupt", seam="engine.fused_repair", at=1,
                calls=1)], seed=4):
            out = call()
        assert not _equal(out, control)
        assert s.stats()["verify_failures"] == 0
    finally:
        set_global_supervisor(prev)
        fallback.set_global_policy(prev_pol)


# ----------------------------------------------------------------------
# health probe / re-promotion properties

def test_repromotion_needs_consecutive_clean_probes(sup, no_plane):
    call = _fused_driver()
    control = call()
    plan = DispatchFaultPlan(
        [DispatchFault("backend_loss", seam="engine.fused_repair",
                       at=1, calls=None)], seed=6)
    prev = arm_plan(plan)
    try:
        assert _equal(call(), control)
        assert sup.demoted
        # fault still armed: probes fail, clean count stays pinned
        assert not sup.tick() and not sup.tick()
        assert sup.stats()["probe_failed"] >= 2
        assert sup.demoted
        plan.clear()
        # promote_after=2: the FIRST clean probe must not promote
        assert not sup.tick()
        assert sup.demoted
        assert sup.tick()                   # the second one does
        assert not sup.demoted
        assert fallback.global_policy().engine() == "xla"
        assert sup.stats()["repromotions"] == 1
    finally:
        arm_plan(prev)


def test_probe_failure_resets_clean_streak(sup, no_plane):
    call = _fused_driver()
    control = call()
    plan = DispatchFaultPlan(
        [DispatchFault("backend_loss", seam="engine.fused_repair",
                       at=1, calls=None)], seed=6)
    prev = arm_plan(plan)
    try:
        assert _equal(call(), control)
        plan.clear()
        assert not sup.tick()               # clean #1
        plan.cleared = False                # the fault flaps back
        assert not sup.tick()               # streak resets
        plan.clear()
        assert not sup.tick()               # clean #1 again
        assert sup.tick()                   # clean #2 -> promoted
    finally:
        arm_plan(prev)


def test_quarantine_reshrinks_plane_and_never_starves(sup):
    """Mesh-member failure: the plane reshrinks 4 -> 2 -> single
    device, then the tier ladder takes over — the dispatch STILL
    completes byte-identically, and re-promotion restores the
    original width."""
    from ceph_tpu.parallel import plane as planemod
    from ceph_tpu.telemetry import recorder
    prev_plane = planemod.set_data_plane(None)
    single = _fused_driver()
    control = single()                      # single-device reference
    try:
        assert planemod.activate(4) is not None
        call = _fused_driver(B=8)
        mesh_control = call()
        with dispatch_faults([DispatchFault(
                "backend_loss", seam="engine.fused_repair", at=1,
                calls=None)], seed=7) as plan:
            out = call()
            assert _equal(out, mesh_control)
            plan.clear()
        st = sup.stats()
        assert st["quarantines"] >= 2       # 4 -> 2 -> single
        assert st["demotions"] >= 1         # then the tier ladder
        assert planemod.data_plane() is None
        triggers = [d["trigger"] for d in
                    recorder.global_flight_recorder().to_dict()
                    ["dumps"]]
        assert "device_quarantined" in triggers
        for _ in range(sup.promote_after + 1):
            sup.tick()
        assert not sup.demoted
        p = planemod.data_plane()
        assert p is not None and p.n_devices == 4   # width restored
        assert _equal(call(), mesh_control)
    finally:
        planemod.set_data_plane(prev_plane)


# ----------------------------------------------------------------------
# the acceptance shape: lose the backend mid-stream through
# repair_batched — byte-identical heal, zero data loss, flight dump,
# logged re-promotion

def test_repair_batched_survives_midstream_backend_loss(sup,
                                                        no_plane):
    from ceph_tpu.chaos import ShardErasure, inject
    from ceph_tpu.codes.stripe import HashInfo, StripeInfo
    from ceph_tpu.codes.stripe import encode as stripe_encode
    from ceph_tpu.recovery.orchestrator import healed
    from ceph_tpu.scrub import repair_batched
    from ceph_tpu.telemetry import recorder
    ec = _mk_ec()
    n = ec.get_chunk_count()
    k = ec.get_data_chunk_count()
    sinfo = StripeInfo(k, k * 512)
    rng = np.random.default_rng(11)
    originals, stores, hinfos = [], [], []
    for i in range(4):
        obj = rng.integers(0, 256, k * 512, np.uint8).tobytes()
        shards = stripe_encode(sinfo, ec, obj)
        hinfo = HashInfo(n)
        hinfo.append(0, shards)
        # two distinct patterns -> two fused pattern batches: the
        # SECOND one loses the backend (a warm seam, mid-stream)
        store, _ = inject(shards, [ShardErasure(shards=[i % 2])],
                          seed=100 + i, chunk_size=sinfo.chunk_size)
        originals.append(shards)
        stores.append(store)
        hinfos.append(hinfo)
    with dispatch_faults([DispatchFault(
            "backend_loss", seam="engine.fused_repair", at=2,
            calls=None)], seed=12) as plan:
        rep = repair_batched(sinfo, ec, stores, hinfos, device=True)
        plan.clear()
    assert rep.pattern_batches == 2
    assert healed(stores, originals)        # zero data loss
    for st, orig in zip(stores, originals):
        for s, buf in orig.items():
            assert bytes(st.shards[s]) == bytes(buf)
    st = sup.stats()
    assert st["demotions"] >= 1 and st["host_completions"] >= 1
    triggers = [d["trigger"] for d in
                recorder.global_flight_recorder().to_dict()["dumps"]]
    assert "backend_demoted" in triggers
    for _ in range(sup.promote_after + 1):
        sup.tick()
    assert sup.stats()["repromotions"] >= 1
    assert not sup.demoted


# ----------------------------------------------------------------------
# DispatchFaultPlan semantics

def test_fault_window_semantics():
    plan = DispatchFaultPlan(
        [DispatchFault("transient", seam="s", at=2, calls=2)], seed=0)
    assert plan.poll("s") is None           # idx 1
    assert plan.poll("other") is None       # counters are per-seam
    assert plan.poll("s").kind == "transient"   # idx 2
    assert plan.poll("s").kind == "transient"   # idx 3
    assert plan.poll("s") is None           # idx 4: window closed
    assert len(plan.fired) == 2


def test_fault_persistent_until_cleared():
    plan = DispatchFaultPlan(
        [DispatchFault("backend_loss", seam="s", at=1, calls=None)],
        seed=0)
    assert plan.pending_persistent()
    for _ in range(5):
        assert plan.poll("s") is not None
    plan.clear()
    assert plan.poll("s") is None
    assert not plan.pending_persistent()


def test_fault_validation():
    with pytest.raises(ValueError):
        DispatchFault("nope")
    with pytest.raises(ValueError):
        DispatchFault("oom", at=0)
    with pytest.raises(ValueError):
        DispatchFault("oom", calls=0)


def test_corrupt_replays_byte_identically():
    out = np.zeros((4, 16), np.uint8)
    flips = []
    for _ in range(2):
        plan = DispatchFaultPlan(
            [DispatchFault("corrupt", seam="s", at=1)], seed=9)
        f = plan.poll("s")
        flips.append(plan.corrupt_output(f, "s", out).tobytes())
    assert flips[0] == flips[1]             # (seed, seam, idx)-pinned
    assert flips[0] != out.tobytes()


def test_classifier():
    assert classify_dispatch_error(
        TransientBackendError("x")) == "transient"
    assert classify_dispatch_error(InjectedOom("s")) == "oom"
    assert classify_dispatch_error(
        InjectedBackendLoss("x")) == "backend_loss"
    assert classify_dispatch_error(DispatchHang("x")) == "backend_loss"
    assert classify_dispatch_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating")) == "oom"
    assert classify_dispatch_error(RuntimeError(
        "UNAVAILABLE: socket closed")) == "backend_loss"
    assert classify_dispatch_error(
        RetryExhausted(3, TransientBackendError("t"))) == "transient"
    # NOT ours: a genuine bug must propagate unclassified
    assert classify_dispatch_error(ValueError("shape")) is None
    assert classify_dispatch_error(RuntimeError("plain bug")) is None


def test_floor_policy_completes_on_twin(no_plane):
    """A policy ALREADY at the numpy floor (no backend initialized at
    all) plus a failing dispatch must complete on the ground-truth
    twin, not re-raise."""
    prev_pol = fallback.set_global_policy(
        fallback.FallbackPolicy(force="numpy"))
    s = DispatchSupervisor(clock=FakeClock())
    prev = set_global_supervisor(s)
    try:
        data = np.arange(32, dtype=np.uint8)

        def body(x):
            return x ^ np.uint8(0xFF)

        with dispatch_faults([DispatchFault(
                "backend_loss", seam="s", at=1, calls=1)], seed=1):
            out = s.dispatch("s", body, (data,), host_fn=body)
        assert np.array_equal(out, body(data))
        assert s.stats()["host_completions"] == 1
        assert s.stats()["demotions"] == 0      # nothing left to demote
    finally:
        set_global_supervisor(prev)
        fallback.set_global_policy(prev_pol)


def test_unclassified_errors_propagate(sup, no_plane):
    def boom():
        raise ValueError("a real bug")

    with pytest.raises(ValueError):
        sup.dispatch("s", boom, ())
    assert sup.stats()["demotions"] == 0


# ----------------------------------------------------------------------
# scenario spec + bench + bench_diff satellites

def test_scenario_spec_roundtrips_dispatch_fault():
    from dataclasses import replace

    from ceph_tpu.scenario.spec import default_scenario
    spec = default_scenario()
    spec = replace(spec, chaos=replace(
        spec.chaos, dispatch_fault="backend_loss",
        dispatch_fault_at=3, dispatch_fault_calls=None))
    again = type(spec).from_json(spec.to_json())
    assert again == spec
    assert again.chaos.dispatch_fault == "backend_loss"
    assert again.chaos.dispatch_fault_calls is None


def test_bench_device_chaos_workload_host(sup, no_plane):
    from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench
    bench = ErasureCodeBench()
    bench.setup(["-p", "jerasure", "-P", "technique=reed_sol_van",
                 "-P", "k=4", "-P", "m=2", "-s", "4096",
                 "--workload", "device-chaos", "--device", "host",
                 "--batch", "2", "--iterations", "1", "-e", "1"])
    res = bench.run()
    assert res["workload"] == "device-chaos"
    assert res["verified"] is True
    assert res["faults_fired"] >= 2
    assert res["supervisor"]["retries"] >= 1
    assert res["supervisor"]["demotions"] >= 1
    assert res["supervisor"]["repromotions"] >= 1
    assert res["demoted_at_end"] is False


def _load_bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff_sup", REPO_ROOT / "tools" / "bench_diff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_flags_device_chaos_regression(tmp_path, capsys):
    """The red fixture: a 60% recovery-under-fault drop must trip the
    sentinel under the device_chaos category's own floor."""
    bd = _load_bench_diff()
    prior = {"metric": "m", "value": 100.0, "git_sha": "aaa",
             "timestamp": "2026-01-01T00:00:00+00:00",
             "device_chaos_rows": {"rs": {"gbps": 1.0}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": prior}))
    cur = {"metric": "m", "value": 100.0, "git_sha": "bbb",
           "timestamp": "2026-02-01T00:00:00+00:00",
           "device_chaos_rows": {"rs": {"gbps": 0.4}}}
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == ["device_chaos:rs"]
    # within the floor passes (green fixture)
    cur["device_chaos_rows"]["rs"]["gbps"] = 0.8
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0


def test_supervisor_audit_entries_registered():
    from ceph_tpu.analysis.entrypoints import registry
    names = {e.name: e for e in registry()}
    assert names["ops.supervisor"].kind == "host"
    assert names["engine.fused_repair_supervised"].kind == "jit"


def test_supervisor_selftest_green():
    from ceph_tpu.ops.supervisor import supervisor_selftest
    st = supervisor_selftest()
    assert st["repromotions"] >= 1 and not st["demoted"]
