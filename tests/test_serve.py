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

@pytest.mark.parametrize("codec", FAMILY_CODECS,
                         ids=[c.name for c in FAMILY_CODECS])
def test_batched_equals_per_request_host(codec):
    """Batched (padded, demuxed) execution is byte-identical to
    per-request execution for every plugin family: ground truth from
    the generator AND a direct per-request surface call both match."""
    spec = small_spec([codec], n=24)
    run = sim_run(spec)
    assert len(run.results) == 24
    assert verify_results(run.results) == []
    reg = ErasureCodePluginRegistry.instance()
    ec = reg.factory(codec.plugin, dict(codec.profile))
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


def test_batched_equals_per_request_device_seam():
    """The jitted serve dispatch seam (engine.serve_dispatch_call)
    returns the same bytes as the per-request device surfaces."""
    spec = small_spec([RS4, SHEC4], n=24)
    run = sim_run(spec, executor="device")
    assert verify_results(run.results) == []
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

def test_500_stream_zero_recompiles_budget_armed():
    """The acceptance gate: a seeded 500+-request mixed (plugin × op ×
    stripe-size) stream through a warmed batcher is byte-identical to
    ground truth, compiles ZERO programs, and never builds a new
    pattern under an armed recompile budget."""
    from ceph_tpu.analysis.jaxpr_audit import _CompileCounter
    from ceph_tpu.codes.engine import global_pattern_cache

    codecs = [
        RS4,
        CodecSpec("rs_k4_m2_8k", "jerasure",
                  {"technique": "reed_sol_van", "k": "4", "m": "2"},
                  8192),
        SHEC4,
    ]
    spec = small_spec(codecs, n=500, seed=13, concurrency=32,
                      pool=4)
    # run 1: cold — compiles the bucket ladder + warms every pattern
    first = sim_run(spec, executor="device")
    assert len(first.results) == 500
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
    assert len(second.results) == 500
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
    """serve.dispatch + serve.dispatch_ragged (jit tier) and
    serve.batcher + serve.pool (host tier) are registered entry points
    and pass the trace rules + the recompile sentinel (warm == 0 for
    the dispatch programs; zero compiles and zero device arrays for
    the bookkeeping)."""
    from ceph_tpu.analysis.entrypoints import registry
    from ceph_tpu.analysis.jaxpr_audit import (audit_entry_point,
                                               run_sentinel)
    ents = {e.name: e for e in registry()}
    assert ents["serve.dispatch"].kind == "jit"
    assert ents["serve.dispatch_ragged"].kind == "jit"
    assert ents["serve.dispatch_ragged_sharded"].kind == "jit"
    assert ents["serve.batcher"].kind == "host"
    assert ents["serve.pool"].kind == "host"
    for name in ("serve.dispatch", "serve.dispatch_ragged",
                 "serve.pool", "serve.batcher"):
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


# ----------------------------------------------------------------------
# paged stripe pool + ragged serving (ISSUE 18)

MIXED_SIZES = (2048, 4096, 8192)


def mixed_codecs(base: CodecSpec):
    """The same (plugin, profile) at three stripe sizes — one ragged
    queue, three dense buckets."""
    return [CodecSpec(f"{base.name}_{s}", base.plugin,
                      dict(base.profile), s) for s in MIXED_SIZES]


def paged_spec(codecs, n=40, seed=7, **kw):
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 512)
    kw.setdefault("pool_pages", 64)
    return small_spec(codecs, n=n, seed=seed, **kw)


@pytest.mark.parametrize("codec", FAMILY_CODECS,
                         ids=[c.name for c in FAMILY_CODECS])
def test_paged_mixed_sizes_byte_identity_host(codec):
    """Mixed stripe sizes co-batched in ONE ragged queue demux
    byte-identical to per-request execution, for every plugin family:
    generator ground truth AND a direct per-request surface call."""
    spec = paged_spec(mixed_codecs(codec), n=30)
    run = sim_run(spec)
    assert len(run.results) == 30
    assert verify_results(run.results) == []
    st = run.batcher.padding_stats()
    assert st["paged"] is True
    # 512 divides every chunk in the mix: zero page-tail padding
    assert st["padding_overhead"] == 0.0
    # the three stripe sizes really co-batched: every fired queue key
    # is chunk-size-free, so sizes share dispatch-log buckets
    assert all(d.get("paged") for d in run.batcher.dispatch_log)
    reg = ErasureCodePluginRegistry.instance()
    ec = reg.factory(codec.plugin, dict(codec.profile))
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


def test_paged_mixed_sizes_device_seam():
    """The jitted ragged seam (engine.serve_dispatch_ragged) serves a
    mixed-size mixed-plugin stream byte-identical to ground truth."""
    spec = paged_spec(mixed_codecs(RS4) + mixed_codecs(SHEC4), n=36,
                      seed=11)
    run = sim_run(spec, executor="device")
    assert len(run.results) == 36
    assert verify_results(run.results) == []
    assert run.batcher.padding_stats()["padding_overhead"] == 0.0


def test_ragged_ops_bytes_and_packed_identity():
    """The ops-layer ragged family (bytes + packed resident layout)
    matches mask-then-dense for a scattered activity mask, and dead
    pages come back zero."""
    from ceph_tpu.ops.pallas_gf import (apply_matrix_best,
                                        apply_matrix_best_ragged,
                                        apply_matrix_packed_best,
                                        apply_matrix_packed_best_ragged,
                                        mask_pages)
    from ceph_tpu.ops.xla_ops import jax_bytes_view, jax_words_view
    reg = ErasureCodePluginRegistry.instance()
    ec = reg.factory(RS4.plugin, dict(RS4.profile))
    ms = ec._matrix_static
    rng = np.random.default_rng(5)
    pool = rng.integers(0, 256, (6, ec.k, 512), dtype=np.uint8)
    mask = np.array([1, 0, 1, 1, 0, 1], np.uint8)
    words = np.asarray(jax_words_view(pool, 8))
    out = np.asarray(jax_bytes_view(
        apply_matrix_best_ragged(words, ms, mask, 8)))
    ref = np.asarray(jax_bytes_view(apply_matrix_best(
        np.asarray(mask_pages(words, mask)), ms, 8)))
    assert np.array_equal(out, ref)
    assert not out[mask == 0].any()
    assert out[mask == 1].any()
    # packed resident twin
    packed = np.ascontiguousarray(
        words.reshape(6, ec.k, -1, 4, 128).transpose(0, 1, 2, 4, 3)
    ).view(np.uint32).reshape(6, ec.k, -1, 128)
    pout = np.asarray(apply_matrix_packed_best_ragged(packed, ms, mask))
    pref = np.asarray(apply_matrix_packed_best(
        np.asarray(mask_pages(packed, mask)), ms))
    assert np.array_equal(pout, pref)
    assert not pout[mask == 0].any()


def test_pool_exhaustion_backpressure():
    """A write that cannot allocate fires the queue (demux reclaims
    every page), then retries — requests keep flowing with the
    backpressure counter as the witness, and bytes stay identical."""
    clock = FakeClock()
    batcher = ContinuousBatcher(clock=clock, executor="host",
                                service_model=lambda b, r: 1e-4,
                                paged=True, page_size=512,
                                pool_pages=3)
    ec = batcher._instance(RS4.plugin, RS4.profile)
    reqs = []
    rng = np.random.default_rng(3)
    for i in range(4):  # 2 pages each, pool holds 3
        pay = rng.integers(0, 256, (ec.k, 1024), dtype=np.uint8)
        reqs.append(EcRequest(op="encode", plugin=RS4.plugin,
                              profile=RS4.profile, stripe_size=4096,
                              payload=pay, req_id=i, arrival=0.0,
                              deadline=99.0))
    fired = batcher.admit(reqs) + batcher.flush()
    assert sorted(r.request.req_id for r in fired) == [0, 1, 2, 3]
    ps = batcher.pool_stats()
    assert ps["backpressure"] >= 1
    assert ps["used_pages"] == 0 and ps["allocs"] == ps["reclaims"]
    ec.min_xla_bytes = float("inf")
    for res in fired:
        ref = np.asarray(
            ec.encode_chunks_batch(res.request.payload[None]))[0]
        assert np.array_equal(res.output, ref)
    # a single request no empty pool could hold is a sizing error
    big = rng.integers(0, 256, (ec.k, 4096), dtype=np.uint8)
    with pytest.raises(ValueError, match="pool"):
        batcher.admit([EcRequest(op="encode", plugin=RS4.plugin,
                                 profile=RS4.profile,
                                 stripe_size=16384, payload=big,
                                 req_id=9, arrival=0.0, deadline=99.0)])


def test_page_reclaim_after_demux_accounting():
    """Every fire returns its pages at demux: after a full mixed run
    the pools are empty, allocs == reclaims, and the high-water mark
    shows real co-residency happened."""
    spec = paged_spec(mixed_codecs(RS4), n=24)
    run = sim_run(spec)
    assert verify_results(run.results) == []
    ps = run.batcher.pool_stats()
    assert ps["used_pages"] == 0
    assert ps["allocs"] == ps["reclaims"] > 0
    assert ps["high_water"] > 1
    # tail-padding accounting stays byte-based and zero here
    st = run.batcher.padding_stats()
    assert st["padded_stripes"] == 0
    assert st["padded_bytes"] == 0
    # and a non-dividing page size shows nonzero page-tail bytes
    spec2 = paged_spec([CodecSpec("rs_odd", RS4.plugin,
                                  dict(RS4.profile), 4096)],
                       n=8, page_size=768)
    run2 = sim_run(spec2)
    assert verify_results(run2.results) == []
    st2 = run2.batcher.padding_stats()
    assert st2["padded_bytes"] > 0
    assert 0.0 < st2["padding_overhead"] < 1.0


def test_paged_zero_recompiles_budget_armed():
    """The paged acceptance gate: a warmed ragged stream over mixed
    sizes compiles NOTHING on its second run — compile counter at 0
    under an armed PatternCache recompile budget, and the cached-
    program count stays at one program per (op, pattern) queue."""
    from ceph_tpu.analysis.jaxpr_audit import _CompileCounter
    from ceph_tpu.codes.engine import global_pattern_cache

    spec = paged_spec(mixed_codecs(RS4) + mixed_codecs(SHEC4), n=200,
                      seed=13, concurrency=32, pool=4)
    first = sim_run(spec, executor="device")
    assert len(first.results) == 200
    assert verify_results(first.results) == []
    cache = global_pattern_cache()
    prev_budget = cache.recompile_budget
    cache.recompile_budget = cache.builds
    try:
        with _CompileCounter() as counter:
            second = sim_run(spec, executor="device")
    finally:
        cache.recompile_budget = prev_budget
    assert len(second.results) == 200
    assert verify_results(second.results) == []
    assert counter.count == 0
    assert second.report["stream_compiles"] == 0
    assert first.batcher.dispatch_log == second.batcher.dispatch_log


def test_paged_contention_pinned_acceptance():
    """THE pinned mixed-size contention scenario (ISSUE 18 acceptance):
    same seed, dense rung-ladder baseline vs paged ragged serving —
    padding overhead < 1%, cached-program count strictly below the
    bucket x rung count, GB/s-under-SLO at least matching."""
    codecs = (mixed_codecs(RS4)
              + [CodecSpec("rs_k4_m2_6k", RS4.plugin,
                           dict(RS4.profile), 24576)]
              + mixed_codecs(SHEC4))
    base = dict(n=120, seed=29, concurrency=24)
    dense = sim_run(small_spec(codecs, ladder=(1, 2, 4, 8), **base))
    paged = sim_run(paged_spec(codecs, ladder=(1, 2, 4, 8),
                               pool_pages=96, **base))
    assert verify_results(dense.results) == []
    assert verify_results(paged.results) == []
    dstats = dense.batcher.padding_stats()
    pstats = paged.batcher.padding_stats()
    # the contention mix forces real dense padding; paged pays none
    assert dstats["padding_overhead"] > 0.05
    assert pstats["padding_overhead"] < 0.01
    # program-count collapse: |patterns| strictly below |buckets|x|rungs|
    assert paged.batcher.cached_program_count() < \
        dense.batcher.cached_program_count()
    # serving throughput under SLO at least matches the baseline
    assert paged.report["gbps_under_slo"] >= \
        dense.report["gbps_under_slo"]


def test_paged_determinism_and_spec_roundtrip():
    """Paged runs are as deterministic as dense ones (same seed ⇒ same
    dispatch log + SLO report) and the paged fields survive the
    TrafficSpec dict round-trip."""
    spec = paged_spec(mixed_codecs(RS4), n=30)
    a = sim_run(spec)
    b = sim_run(spec)
    assert a.batcher.dispatch_log == b.batcher.dispatch_log
    assert json.dumps(a.report, sort_keys=True) == \
        json.dumps(b.report, sort_keys=True)
    spec2 = TrafficSpec.from_dict(spec.to_dict())
    assert spec2.paged is True
    assert spec2.page_size == 512 and spec2.pool_pages == 64
    # dense specs round-trip their default too
    spec3 = TrafficSpec.from_dict(small_spec([RS4]).to_dict())
    assert spec3.paged is False


def test_pool_selftest_and_interleave_roundtrip():
    """The serve.pool host entry's selftest is green, and the
    interleaved split/join honors clay's sub-chunk coupling (every
    page a valid mini-chunk)."""
    from ceph_tpu.serve import (PagedStripePool, PoolExhausted,
                                pool_selftest, split_pages, join_pages)
    st = pool_selftest()
    assert st["ok"] and st["round_trips"] > 0
    rng = np.random.default_rng(2)
    arr = rng.integers(0, 256, (5, 2048), dtype=np.uint8)
    pages = split_pages(arr, 512, interleave=8)
    assert pages.shape == (4, 5, 512)
    assert np.array_equal(join_pages(pages, 2048, interleave=8), arr)
    # non-multiple page size is rejected up front
    with pytest.raises(ValueError, match="interleave"):
        split_pages(arr, 516, interleave=8)
    # duplicate staging is rejected
    pool = PagedStripePool(4, 5, 512)
    pool.write("x", arr)
    with pytest.raises(ValueError, match="already staged"):
        pool.write("x", arr)
    with pytest.raises(PoolExhausted):
        pool.write("y", arr)


def test_bench_diff_serving_padding_red_green(tmp_path, capsys):
    """Satellite: bench_diff's `serving_padding` category is the one
    LOWER-is-better series — a paged row whose padding_overhead
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
             "serving_rows": {"serving_mixed_paged": {
                 "gbps": 1.0, "gbps_under_slo": 1.0,
                 "padding_overhead": 0.005, "paged": True}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": prior}))
    # red: padding reinflates 0.005 -> 0.2 (dense-bucket territory)
    cur = {"metric": "m", "value": 100.0, "git_sha": "bbb",
           "timestamp": "2026-02-01T00:00:00+00:00",
           "serving_rows": {"serving_mixed_paged": {
               "gbps": 1.0, "gbps_under_slo": 1.0,
               "padding_overhead": 0.2, "paged": True}}}
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    rc = bd.main(["--repo", str(tmp_path), "--json"])
    assert rc == 4
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == [
        "serving_padding:serving_mixed_paged"]
    # green: 0.005 -> 0.008 sits inside the absolute near-zero slack
    cur["serving_rows"]["serving_mixed_paged"][
        "padding_overhead"] = 0.008
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0
    capsys.readouterr()
    # and a genuine paged improvement (0.005 -> 0.0) reads as ok/new
    # direction, never a regression
    cur["serving_rows"]["serving_mixed_paged"][
        "padding_overhead"] = 0.0
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(cur))
    assert bd.main(["--repo", str(tmp_path)]) == 0
