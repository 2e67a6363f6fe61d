"""Serving front-end tier-1 slice (ceph_tpu/serve, docs/SERVING.md).

The acceptance axes of ISSUE 7:

- FakeClock determinism: same seed ⇒ byte-identical batch composition
  AND byte-identical SLO report.
- Byte identity: batched execution ≡ per-request execution for all
  five plugin families (host tier) and for the device dispatch seam.
- Zero warm recompiles: a 500-request mixed (plugin × op ×
  stripe-size) stream after bucket-ladder warmup compiles NOTHING —
  compile monitor at 0 AND the armed PatternCache recompile budget
  silent.
- Deadline-slack dispatch: a bucket fires when full or when its
  oldest request runs out of slack, earliest deadline first.
- The persistent compilation cache replays warm across processes
  (cache-miss sentinel at 0 in the second process).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ceph_tpu.codes.registry import ErasureCodePluginRegistry
from ceph_tpu.serve import (
    AdmissionQueue,
    CodecSpec,
    ContinuousBatcher,
    EcRequest,
    LoadGenerator,
    SloPolicy,
    TrafficSpec,
    default_spec,
    run_serving_scenario,
    rung_for,
    throughput_service_model,
    verify_results,
)
from ceph_tpu.utils.retry import FakeClock

RS4 = CodecSpec("rs_k4_m2", "jerasure",
                {"technique": "reed_sol_van", "k": "4", "m": "2"}, 4096)
SHEC4 = CodecSpec("shec_k4_m3_c2", "shec",
                  {"k": "4", "m": "3", "c": "2"}, 4096)

FAMILY_CODECS = [
    RS4,
    CodecSpec("isa_k4_m2", "isa", {"k": "4", "m": "2"}, 4096),
    SHEC4,
    CodecSpec("lrc_k4_m2_l3", "lrc",
              {"k": "4", "m": "2", "l": "3"}, 4096),
    CodecSpec("clay_k4_m2_d5", "clay",
              {"k": "4", "m": "2", "d": "5"}, 4096),
]


MIXED_SIZES = (2048, 4096, 8192)


def mixed_codecs(base: CodecSpec):
    """The same (plugin, profile) at three stripe sizes: three dense
    buckets per pattern, one per chunk size."""
    return [CodecSpec(f"{base.name}_{s}", base.plugin,
                      dict(base.profile), s) for s in MIXED_SIZES]


def small_spec(codecs, n=40, seed=7, **kw):
    kw.setdefault("ladder", (1, 2, 4, 8))
    kw.setdefault("concurrency", 16)
    return TrafficSpec(seed=seed, n_requests=n, codecs=list(codecs),
                       **kw)


def sim_run(spec, executor="host", **kw):
    return run_serving_scenario(
        spec, clock=FakeClock(), executor=executor,
        service_model=throughput_service_model(), **kw)


# ----------------------------------------------------------------------
# determinism

@pytest.mark.parametrize("arrival", ["closed", "open"])
def test_fakeclock_determinism(arrival):
    """Same seed ⇒ byte-identical batch composition (the dispatch log:
    bucket, occupancy, rung, request ids in order) and byte-identical
    SLO report, for both arrival processes."""
    spec = small_spec([RS4, SHEC4], arrival=arrival)
    a = sim_run(spec)
    b = sim_run(spec)
    assert a.batcher.dispatch_log == b.batcher.dispatch_log
    assert json.dumps(a.report, sort_keys=True) == \
        json.dumps(b.report, sort_keys=True)
    assert len(a.results) == spec.n_requests
    # and a different seed changes the composition (the log is a real
    # witness, not a constant)
    spec2 = small_spec([RS4, SHEC4], arrival=arrival, seed=8)
    c = sim_run(spec2)
    assert c.batcher.dispatch_log != a.batcher.dispatch_log


# ----------------------------------------------------------------------
# byte identity, all five families

def _chunk_sizes(run):
    """(chunk sizes of the fired buckets, of the requests served)."""
    ec_of = run.batcher._instance
    served = {ec_of(r.request.plugin, r.request.profile).get_chunk_size(
        r.request.stripe_size) for r in run.results}
    return {b.chunk_size for b in run.batcher._buckets.values()}, served


@pytest.mark.parametrize(
    "codecs",
    [pytest.param([c], id=c.name) for c in FAMILY_CODECS]
    + [pytest.param(mixed_codecs(c), id=f"{c.name}-mixed_sizes")
       for c in FAMILY_CODECS])
def test_batched_equals_per_request_host(codecs):
    """Batched (padded, demuxed) execution is byte-identical to
    per-request execution for every plugin family, at one stripe size
    and at three (one dense bucket per chunk size): ground truth from
    the generator AND a direct per-request surface call both match."""
    spec = small_spec(codecs, n=24)
    run = sim_run(spec)
    assert len(run.results) == 24
    assert verify_results(run.results) == []
    buckets, served = _chunk_sizes(run)
    assert buckets == served
    assert len(served) == len(codecs)
    reg = ErasureCodePluginRegistry.instance()
    ec = reg.factory(codecs[0].plugin, dict(codecs[0].profile))
    ec.min_xla_bytes = float("inf")
    for res in run.results[:6]:
        req = res.request
        if req.op == "encode":
            ref = np.asarray(
                ec.encode_chunks_batch(req.payload[None]))[0]
            assert np.array_equal(res.output, ref)
        else:
            ref = np.asarray(ec.decode_chunks_batch(
                req.payload[None], req.available, req.erased))[0]
            rec = res.output[0] if req.op == "repair" else res.output
            assert np.array_equal(rec, ref)


@pytest.mark.parametrize("codecs", [
    pytest.param([RS4, SHEC4], id="two_codecs"),
    pytest.param(mixed_codecs(RS4) + mixed_codecs(SHEC4),
                 id="mixed_sizes")])
def test_batched_equals_per_request_device_seam(codecs):
    """The jitted serve dispatch seam (engine.serve_dispatch_call)
    returns the same bytes as the per-request device surfaces, for
    one stripe size and for a mix of three."""
    spec = small_spec(codecs, n=24)
    run = sim_run(spec, executor="device")
    assert verify_results(run.results) == []
    buckets, served = _chunk_sizes(run)
    assert buckets == served
    reg = ErasureCodePluginRegistry.instance()
    for res in run.results[:8]:
        req = res.request
        ec = reg.factory(req.plugin, dict(req.profile))
        if req.op == "encode":
            ref = np.asarray(ec.encode_chunks_jax(req.payload[None]))[0]
            assert np.array_equal(res.output, ref)
        elif req.op == "decode":
            ref = np.asarray(ec.decode_chunks_jax(
                req.payload[None], req.available, req.erased))[0]
            assert np.array_equal(res.output, ref)


# ----------------------------------------------------------------------
# zero warm recompiles + armed recompile budget, 500-request stream

@pytest.mark.parametrize("codecs,n", [
    pytest.param([RS4,
                  CodecSpec("rs_k4_m2_8k", "jerasure",
                            {"technique": "reed_sol_van", "k": "4",
                             "m": "2"}, 8192),
                  SHEC4], 500, id="rs_two_sizes_shec"),
    pytest.param(mixed_codecs(RS4) + mixed_codecs(SHEC4), 200,
                 id="mixed_sizes")])
def test_500_stream_zero_recompiles_budget_armed(codecs, n):
    """The acceptance gate: a seeded mixed (plugin × op × stripe-size)
    stream through a warmed batcher is byte-identical to ground truth,
    compiles ZERO programs, and never builds a new pattern under an
    armed recompile budget (500 requests; 200 over three sizes of
    two codecs)."""
    from ceph_tpu.analysis.jaxpr_audit import _CompileCounter
    from ceph_tpu.codes.engine import global_pattern_cache

    spec = small_spec(codecs, n=n, seed=13, concurrency=32, pool=4)
    # run 1: cold — compiles the bucket ladder + warms every pattern
    first = sim_run(spec, executor="device")
    assert len(first.results) == n
    assert verify_results(first.results) == []
    # arm: any pattern build past this point raises loudly
    cache = global_pattern_cache()
    prev_budget = cache.recompile_budget
    cache.recompile_budget = cache.builds
    try:
        with _CompileCounter() as counter:
            second = sim_run(spec, executor="device")
    finally:
        cache.recompile_budget = prev_budget
    assert len(second.results) == n
    assert verify_results(second.results) == []
    # the whole warm pipeline — ladder warmup included — compiled
    # nothing (counter covers the entire second run)
    assert counter.count == 0
    assert second.report["stream_compiles"] == 0
    # batch composition is identical run to run (same seed)
    assert first.batcher.dispatch_log == second.batcher.dispatch_log


# ----------------------------------------------------------------------
# deadline-slack dispatch

def _encode_req(ec, codec, req_id, seed=0):
    rng = np.random.default_rng(seed + req_id)
    k = ec.get_data_chunk_count()
    chunk = ec.get_chunk_size(codec.stripe_size)
    return EcRequest(op="encode", plugin=codec.plugin,
                     profile=codec.profile,
                     stripe_size=codec.stripe_size,
                     payload=rng.integers(0, 256, (k, chunk),
                                          dtype=np.uint8),
                     req_id=req_id)


def test_deadline_slack_dispatch_ordering():
    """A non-full bucket holds until its oldest request's slack runs
    out; due buckets fire earliest deadline first."""
    clock = FakeClock()
    codec_a, codec_b = RS4, SHEC4
    reg = ErasureCodePluginRegistry.instance()
    ec_a = reg.factory(codec_a.plugin, dict(codec_a.profile))
    ec_b = reg.factory(codec_b.plugin, dict(codec_b.profile))
    queue = AdmissionQueue(clock=clock, slo=SloPolicy(
        deadlines={"encode": 1.0, "decode": 1.0, "repair": 1.0}))
    batcher = ContinuousBatcher(
        clock=clock, ladder=(4,), executor="host",
        service_model=lambda b, rung: 1e-4, min_slack=1e-3)
    # request A: 1.0 s slack; request B (different bucket): 0.5 s
    ra = _encode_req(ec_a, codec_a, 0)
    rb = _encode_req(ec_b, codec_b, 1)
    rb.deadline = 0.5
    assert queue.submit(ra) and queue.submit(rb)
    # not due yet: nothing fires
    assert batcher.poll(queue) == []
    assert batcher.pending() == 2
    # just before B's fire point (deadline - margin = 0.499): holding
    clock.now = 0.498
    assert batcher.poll() == []
    # past B's fire point but before A's: only B fires, and firing
    # margin ahead of the deadline lands the completion inside it
    clock.now = 0.4995
    fired = batcher.poll()
    assert [r.request.req_id for r in fired] == [1]
    assert fired[0].deadline_met
    # past A's fire point: A fires; log shows B before A
    clock.now = 0.9995
    fired = batcher.poll()
    assert [r.request.req_id for r in fired] == [0]
    ids = [d["req_ids"] for d in batcher.dispatch_log]
    assert ids == [[1], [0]]


def test_full_bucket_fires_immediately():
    """A bucket reaching the top rung dispatches inside admit() —
    continuous batching never holds a full batch for the next poll."""
    clock = FakeClock()
    ec = ErasureCodePluginRegistry.instance().factory(
        RS4.plugin, dict(RS4.profile))
    batcher = ContinuousBatcher(clock=clock, ladder=(1, 2),
                                executor="host",
                                service_model=lambda b, r: 1e-4)
    reqs = [_encode_req(ec, RS4, i) for i in range(2)]
    for r in reqs:
        r.arrival = 0.0
        r.deadline = 99.0
    fired = batcher.admit(reqs)
    assert [r.request.req_id for r in fired] == [0, 1]
    assert fired[0].batch_rung == 2
    assert fired[0].batch_occupancy == 2


def test_padding_and_admission_accounting():
    """Padding waste is counted per dispatch (occupancy 3 → rung 4 =
    one padded stripe) and the queue rejects above capacity."""
    clock = FakeClock()
    ec = ErasureCodePluginRegistry.instance().factory(
        RS4.plugin, dict(RS4.profile))
    batcher = ContinuousBatcher(clock=clock, ladder=(1, 2, 4),
                                executor="host",
                                service_model=lambda b, r: 1e-4)
    reqs = [_encode_req(ec, RS4, i) for i in range(3)]
    for r in reqs:
        r.arrival = 0.0
        r.deadline = 0.0  # due immediately
    batcher.admit(reqs)
    fired = batcher.poll()
    assert len(fired) == 3
    assert fired[0].batch_rung == 4
    stats = batcher.padding_stats()
    assert stats["stripes"] == 3
    assert stats["padded_stripes"] == 1
    assert stats["padding_overhead"] == 0.25
    # padded rows never leak into results
    assert all(r.request.req_id in (0, 1, 2) for r in fired)
    # admission control: capacity 2 rejects the third submit
    q = AdmissionQueue(clock=clock, capacity=2)
    small = [_encode_req(ec, RS4, i + 10) for i in range(3)]
    assert q.submit(small[0]) and q.submit(small[1])
    assert not q.submit(small[2])
    assert q.rejected == 1 and q.admitted == 2


def test_slo_report_shape_and_padding_section():
    """The SLO report carries per-op-class percentiles, miss rates and
    GB/s-under-SLO plus the batcher's padding accounting."""
    spec = small_spec([RS4], n=20)
    run = sim_run(spec)
    rep = run.report
    for f in ("requests", "deadline_miss_rate", "gbps",
              "gbps_under_slo", "p50_ms", "p99_ms", "p999_ms",
              "op_classes", "padding", "admitted", "rejected"):
        assert f in rep, f
    assert rep["requests"] == 20
    for op, row in rep["op_classes"].items():
        assert op in ("encode", "decode", "repair")
        assert row["requests"] >= 1
        assert row["p50_ms"] is not None
        assert "queue_wait" in row
    assert rep["padding"]["dispatches"] == \
        run.batcher.padding_stats()["dispatches"]
    # under-SLO throughput can never exceed raw throughput
    assert rep["gbps_under_slo"] <= rep["gbps"]


# ----------------------------------------------------------------------
# audit registration

def test_serve_entries_registered_and_green():
    """serve.dispatch (jit tier) and serve.batcher (host tier) are
    registered entry points and pass the trace rules + the recompile
    sentinel (warm == 0 for the dispatch program; zero compiles and
    zero device arrays for the bookkeeping)."""
    from ceph_tpu.analysis.entrypoints import registry
    from ceph_tpu.analysis.jaxpr_audit import (audit_entry_point,
                                               run_sentinel)
    ents = {e.name: e for e in registry()}
    assert ents["serve.dispatch"].kind == "jit"
    assert ents["serve.batcher"].kind == "host"
    assert not [n for n in ents if n.startswith("serve.")
                and n not in ("serve.dispatch", "serve.dispatch_sharded",
                              "serve.batcher")]
    for name in ("serve.dispatch", "serve.batcher"):
        e = ents[name]
        built = e.build()
        audit = audit_entry_point(e, built)
        assert audit.findings == [], (name, audit.findings)
        s = run_sentinel(e, built)
        assert s.findings == [], (name, s.findings)
        assert s.warm_compiles == 0


# ----------------------------------------------------------------------
# persistent compilation cache (two-process replay)

_CACHE_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from ceph_tpu.utils.compile_cache import initialize_compile_cache
from ceph_tpu.telemetry import global_metrics

assert initialize_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
from ceph_tpu.codes.engine import serve_dispatch_call
from ceph_tpu.codes.registry import ErasureCodePluginRegistry
ec = ErasureCodePluginRegistry.instance().factory(
    "jerasure", {"technique": "reed_sol_van", "k": "2", "m": "1"})
call = serve_dispatch_call(ec, "encode")
out = call(np.zeros((2, 2, 512), np.uint8))
np.asarray(out)
reg = global_metrics()
print(json.dumps({
    "hits": reg.counter_value("jax_persistent_cache_hits"),
    "misses": reg.counter_value("jax_persistent_cache_misses"),
}))
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_second_process_replays_warm(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the persistent compilation
    cache: the first process pays the compiles (cache misses > 0), a
    second process replays every program from disk — the warm-compile
    sentinel (persistent-cache misses) at 0."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    env["JAX_PLATFORMS"] = "cpu"

    def run_once():
        r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=_REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run_once()
    assert cold["misses"] > 0
    warm = run_once()
    assert warm["misses"] == 0
    assert warm["hits"] > 0
    from ceph_tpu.utils.compile_cache import cache_entries
    assert cache_entries(str(tmp_path / "cc")) > 0


_PLACEMENT_SCRIPT = r"""
import json
import jax
from ceph_tpu.utils.compile_cache import initialize_compile_cache
d = initialize_compile_cache()
print(json.dumps({"dir": d,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [False, True],
                         ids=["default-path", "env-var"])
def test_compile_cache_placement_rule(tmp_path, env_dir):
    """The cache is JAX_COMPILATION_CACHE_DIR when set (jax reads it;
    no other dir is set in code), else the fixed <checkout>/.jax_cache
    — never a temp name, a PID or the time."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(_REPO, ".jax_cache")
    if env_dir:
        want = str(tmp_path / "elsewhere")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    r = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=120, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"dir": want, "jax": want}


# ----------------------------------------------------------------------
# odds and ends

def test_rung_for_and_ladder_validation():
    assert rung_for(1, (1, 4, 16)) == 1
    assert rung_for(2, (1, 4, 16)) == 4
    assert rung_for(16, (1, 4, 16)) == 16
    # occupancy above the top rung maps to the TOP rung (the batcher
    # splits oversized admissions into top-rung batches); the legacy
    # strict contract still raises for callers that opt in
    assert rung_for(17, (1, 4, 16)) == 16
    assert rung_for(1000, (1, 4, 16)) == 16
    with pytest.raises(ValueError, match="exceeds top rung"):
        rung_for(17, (1, 4, 16), strict=True)
    with pytest.raises(ValueError, match="increasing"):
        ContinuousBatcher(ladder=(4, 1), executor="host")


def test_oversized_occupancy_splits_into_top_rung_batches():
    """A bucket holding more requests than the top rung fires in
    top-rung slices instead of raising (the legacy bare ValueError) —
    every slice rides an already-warmed shape and every request gets
    its result."""
    clock = FakeClock()
    ec = ErasureCodePluginRegistry.instance().factory(
        RS4.plugin, dict(RS4.profile))
    batcher = ContinuousBatcher(clock=clock, ladder=(1, 2, 4),
                                executor="host",
                                service_model=lambda b, r: 1e-4)
    reqs = [_encode_req(ec, RS4, i) for i in range(11)]
    for r in reqs:
        r.arrival = 0.0
        r.deadline = 99.0
    b = batcher._bucket_for(reqs[0])
    b.requests.extend(reqs)  # oversized burst, bypassing admit's fire
    fired = batcher.flush()
    assert sorted(r.request.req_id for r in fired) == list(range(11))
    assert [d["occupancy"] for d in batcher.dispatch_log] == [4, 4, 3]
    assert [d["rung"] for d in batcher.dispatch_log] == [4, 4, 4]
    # results demux from their own slice, byte-identical
    ec.min_xla_bytes = float("inf")
    for res in fired:
        ref = np.asarray(
            ec.encode_chunks_batch(res.request.payload[None]))[0]
        assert np.array_equal(res.output, ref)


def test_request_validation():
    with pytest.raises(ValueError, match="not in"):
        EcRequest(op="scrub", plugin="jerasure", profile={},
                  stripe_size=4096, payload=np.zeros((2, 2), np.uint8))
    with pytest.raises(ValueError, match="erased pattern"):
        EcRequest(op="decode", plugin="jerasure", profile={},
                  stripe_size=4096, payload=np.zeros((2, 2), np.uint8))


def test_default_spec_is_mixed_and_seeded():
    spec = default_spec(seed=3, n_requests=16, stripe_size=4096)
    assert {c.plugin for c in spec.codecs} == \
        {"jerasure", "shec", "clay"}
    gen = LoadGenerator(spec)
    reqs, _ = gen.generate()
    assert len(reqs) == 16
    assert {r.op for r in reqs} <= {"encode", "decode", "repair"}
    # ids are stream-ordered (the determinism witness relies on it)
    assert [r.req_id for r in reqs] == list(range(16))


@pytest.mark.parametrize("field,value", [
    ("paged", True), ("page_size", 512), ("pool_pages", 64)])
def test_traffic_spec_refuses_removed_fields(field, value):
    """A saved spec that still names a knob of the removed page-pool
    serving mode is refused, naming the field, instead of running on
    the dense buckets without saying so."""
    d = small_spec([RS4]).to_dict()
    assert TrafficSpec.from_dict(d).to_dict() == d
    d[field] = value
    with pytest.raises(ValueError, match=field):
        TrafficSpec.from_dict(d)


def test_bench_diff_serving_padding_red_green(tmp_path, capsys):
    """Satellite: bench_diff's `serving_padding` category is the one
    LOWER-is-better series — a serving row whose padding_overhead
    reinflates past the floor trips rc 4; movement inside the
    absolute near-zero slack stays green."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_diff_serve",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_diff.py"))
    bd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bd)
    prior = {"metric": "m", "value": 100.0, "git_sha": "aaa",
             "timestamp": "2026-01-01T00:00:00+00:00",
             "serving_rows": {"serving_mixed_closed": {
                 "gbps": 1.0, "gbps_under_slo": 1.0,
                 "padding_overhead": 0.05}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": prior}))
    # red: padding reinflates 0.05 -> 0.2
    cur = {"metric": "m", "value": 100.0, "git_sha": "bbb",
           "timestamp": "2026-02-01T00:00:00+00:00",
           "serving_rows": {"serving_mixed_closed": {
               "gbps": 1.0, "gbps_under_slo": 1.0,
               "padding_overhead": 0.2}}}
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == [
        "serving_padding:serving_mixed_closed"]
    # green: 0.05 -> 0.055 sits inside the floor
    cur["serving_rows"]["serving_mixed_closed"][
        "padding_overhead"] = 0.055
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0
    capsys.readouterr()
    # and a genuine improvement (0.05 -> 0.0) never reads as a
    # regression
    cur["serving_rows"]["serving_mixed_closed"][
        "padding_overhead"] = 0.0
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0
