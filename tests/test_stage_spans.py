"""Stage spans of the served EC path and bulk CRUSH: each span feeds a
self-time histogram, fires carry their reason, a request's queue wait
splits into admission-queue and bucket time, and the bulk evaluator
counts its host-residue lanes."""

import numpy as np
import pytest

from ceph_tpu import telemetry
from ceph_tpu.serve import AdmissionQueue, ContinuousBatcher, EcRequest
from ceph_tpu.telemetry import MetricsRegistry, SpanTracer
from ceph_tpu.telemetry import spans as spans_mod
from ceph_tpu.utils.retry import FakeClock

RS21 = {"technique": "reed_sol_van", "k": "2", "m": "1"}
SERVICE_S = 0.5
STAGE_HISTS = ("serve_pack_seconds", "serve_device_wait_seconds",
               "serve_d2h_seconds", "serve_demux_seconds")
REMOVED = ("serve_padded_stripes", "serve_page_tail_bytes",
           "serve_warmup_dispatches", "serve_pool_live_pages",
           "serve_queue_depth")


@pytest.fixture
def world():
    """A fresh registry and a FakeClock tracer as the process globals;
    the batcher and the queue share the clock."""
    clk = FakeClock()
    prev_reg = telemetry.set_global_metrics(MetricsRegistry())
    prev_tr = telemetry.set_global_tracer(
        SpanTracer(clock=clk, annotate=False))
    try:
        yield clk
    finally:
        telemetry.set_global_metrics(prev_reg)
        telemetry.set_global_tracer(prev_tr)


def _hist(name):
    """(count, sum) over every labelled series of a histogram."""
    (body,) = telemetry.global_metrics().dump().values()
    c = s = 0
    for k, v in body.items():
        if k == name or k.startswith(name + "{"):
            c += v["count"]
            s += v["sum"]
    return c, s


def _series(prefix):
    (body,) = telemetry.global_metrics().dump().values()
    return {k: v for k, v in body.items()
            if k == prefix or k.startswith(prefix + "{")}


def _req(stripe, rng):
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry.instance().factory("jerasure",
                                                      dict(RS21))
    chunk = ec.get_chunk_size(stripe)
    return EcRequest(op="encode", plugin="jerasure", profile=RS21,
                     stripe_size=stripe,
                     payload=rng.integers(0, 256, (2, chunk), np.uint8))


def _device_batcher(clk, **kw):
    return ContinuousBatcher(clock=clk, ladder=(1, 4), executor="device",
                             service_model=lambda b, r: SERVICE_S, **kw)


def test_dense_device_stream_stage_histograms(world):
    """One bucket fills (fired by ``full`` inside ``admit``); the full
    fire's service time uses up the other bucket's slack, so the same
    poll fires it by ``deadline``: one sample per dispatch in each
    stage histogram, reasons summing to the dispatches, and the full
    fire nested in ``serve.admit`` left out of its self time."""
    clk = world
    rng = np.random.default_rng(1)
    b = _device_batcher(clk)
    q = AdmissionQueue(clock=clk)
    for _ in range(4):
        assert q.submit(_req(256, rng))
    assert q.submit(_req(512, rng))
    out = b.poll(q)
    assert len(out) == 5 and b.dispatches == 2
    assert clk.now == pytest.approx(2 * SERVICE_S)
    for name in STAGE_HISTS:
        assert _hist(name)[0] == b.dispatches, name
    # one admit call; the nested fire advanced the clock, not admit
    assert _hist("serve_admit_seconds") == (1, 0.0)
    root, late = telemetry.global_tracer().finished
    assert late.name == "serve.batch"
    assert late.attrs["reason"] == "deadline"
    assert root.name == "serve.admit"
    (batch,) = root.children
    assert batch.name == "serve.batch" and batch.attrs["reason"] == "full"
    assert batch.duration == pytest.approx(SERVICE_S)
    assert [c.name for c in batch.children] == [
        "serve.pack", "serve.dispatch", "serve.wait", "serve.d2h",
        "serve.demux"]
    reasons = _series("serve_dispatches")
    assert reasons == {"serve_dispatches{op=encode,reason=deadline}": 1,
                       "serve_dispatches{op=encode,reason=full}": 1}
    assert sum(reasons.values()) == b.dispatches


def test_batch_wait_plus_admission_wait_is_queue_wait(world):
    """Per request: (drained - arrival) + batch wait == queue wait, and
    the window sums of the histograms agree."""
    clk = world
    rng = np.random.default_rng(2)
    b = _device_batcher(clk)
    q = AdmissionQueue(clock=clk)
    reqs = [_req(256, rng) for _ in range(2)]
    q.submit(reqs[0])
    clk.sleep(0.03)
    q.submit(reqs[1])
    clk.sleep(0.02)
    assert b.poll(q) == []               # drained, not yet due
    assert all(r.drained == pytest.approx(0.05) for r in reqs)
    clk.sleep(0.25)
    out = b.poll(q)
    assert len(out) == 2
    admission = 0.0
    for res in out:
        r = res.request
        admission += r.drained - r.arrival
        assert res.queue_wait == pytest.approx(
            (r.drained - r.arrival) + (clk.now - SERVICE_S - r.drained))
    n_b, s_b = _hist("serve_batch_wait_seconds")
    n_q, s_q = _hist("serve_queue_wait_seconds")
    assert n_b == n_q == 2
    assert s_b + admission == pytest.approx(s_q)
    assert s_b == pytest.approx(2 * 0.25)


def test_paged_reasons_and_removed_series(world):
    """The ragged path fires by ``backpressure`` and ``flush``, gets
    the stage spans where its stage exists (no pack: the pool is staged
    at admission), and none of the removed series is written."""
    clk = world
    rng = np.random.default_rng(3)
    b = ContinuousBatcher(clock=clk, executor="device",
                          service_model=lambda q, r: 1e-4, paged=True,
                          page_size=256, pool_pages=3)
    q = AdmissionQueue(clock=clk)
    for _ in range(2):                   # 2 pages each, the pool holds 3
        q.submit(_req(1024, rng))
    b.warmup([_req(1024, rng)])
    out = b.poll(q) + b.flush()
    assert len(out) == 2 and b.dispatches == 2
    assert _series("serve_dispatches") == {
        "serve_dispatches{op=encode,reason=backpressure}": 1,
        "serve_dispatches{op=encode,reason=flush}": 1}
    for name in STAGE_HISTS[1:]:
        assert _hist(name)[0] == b.dispatches, name
    assert _hist("serve_pack_seconds")[0] == 0
    dense = _device_batcher(clk)
    dense.warmup([_req(256, rng)])
    for _ in range(2):
        q.submit(_req(256, rng))
    dense.poll(q)
    dense.flush()
    assert dense.padding_stats()["padded_stripes"] == 2
    (body,) = telemetry.global_metrics().dump().values()
    for name in REMOVED:
        assert not any(k == name or k.startswith(name + "{")
                       for k in body), name


def test_host_executor_has_no_device_stages(world):
    """The host tier computes in numpy: no wait and no D2H stage."""
    clk = world
    rng = np.random.default_rng(4)
    b = ContinuousBatcher(clock=clk, ladder=(1, 4), executor="host",
                          service_model=lambda b, r: 1e-3)
    b.admit([_req(256, rng) for _ in range(4)])
    assert b.dispatches == 1
    assert _hist("serve_pack_seconds")[0] == 1
    assert _hist("serve_demux_seconds")[0] == 1
    assert _hist("serve_device_wait_seconds")[0] == 0
    assert _hist("serve_d2h_seconds")[0] == 0


def test_span_metric_records_self_time():
    """``metric=`` observes duration minus the child spans, labelled
    by the attrs given at open (late attrs stay out of the series)."""
    clk = FakeClock()
    prev = telemetry.set_global_metrics(MetricsRegistry())
    try:
        tr = SpanTracer(clock=clk, annotate=False)
        with tr.span("outer", metric="t_outer_seconds", op="x") as sp:
            sp.attrs["lanes"] = 7
            clk.sleep(0.25)
            with tr.span("inner", metric="t_inner_seconds"):
                clk.sleep(1.0)
            with tr.span("plain"):
                clk.sleep(0.5)
            clk.sleep(0.125)
        (root,) = tr.finished
        assert root.duration == pytest.approx(1.875)
        assert root.self_time == pytest.approx(0.375)
        (body,) = telemetry.global_metrics().dump().values()
        assert set(body) == {"t_outer_seconds{op=x}", "t_inner_seconds"}
        assert body["t_outer_seconds{op=x}"]["sum"] == pytest.approx(0.375)
        assert body["t_inner_seconds"]["sum"] == pytest.approx(1.0)
        telemetry.set_enabled(False)
        try:
            with tr.span("off", metric="t_off_seconds"):
                clk.sleep(1.0)
        finally:
            telemetry.set_enabled(True)
        (body,) = telemetry.global_metrics().dump().values()
        assert "t_off_seconds" not in body
    finally:
        telemetry.set_global_metrics(prev)


def test_span_below_debug_level_builds_no_message(monkeypatch):
    """Below level 20 a span reads the telemetry level once and never
    formats or writes its enter/exit messages."""
    from ceph_tpu.utils.log import set_level

    reads = []
    real = spans_mod.get_level
    monkeypatch.setattr(spans_mod, "get_level",
                        lambda sub: reads.append(sub) or real(sub))

    def no_emit(*a):
        raise AssertionError(f"span message built below level 20: {a}")
    monkeypatch.setattr(spans_mod, "emit", no_emit)
    set_level("telemetry", 19)
    try:
        tr = SpanTracer(clock=FakeClock(), annotate=False)
        with tr.span("a", metric="t_quiet_seconds"):
            with tr.span("b"):
                pass
    finally:
        set_level("telemetry", 1)
    assert reads == ["telemetry", "telemetry"]


def test_bulk_residue_lanes_counted(world):
    """``crush_host_residue_lanes`` counts exactly the lanes the bulk
    evaluator leaves to the host mapper (``return_stats``), one OSD
    out; each rung and the residue walk record one span sample."""
    from ceph_tpu.crush import (CrushBuilder, step_chooseleaf_firstn,
                                step_emit, step_take)
    from ceph_tpu.crush import bulk

    b = CrushBuilder()
    root = b.build_two_level(4, 2)
    b.add_rule(0, [step_take(root), step_chooseleaf_firstn(0, 1),
                   step_emit()])
    w = b.map.device_weights()
    w[3] = 0
    _, _, nf = bulk.bulk_do_rule(b.map, 0, np.arange(300), 3, weight=w,
                                 return_stats=True)
    assert nf > 0
    assert _series("crush_host_residue_lanes") == {
        "crush_host_residue_lanes": nf}
    assert _hist("crush_host_residue_seconds")[0] == 1
    assert _series("crush_rung_seconds")["crush_rung_seconds{rung=0}"][
        "count"] == 1
    (root_span,) = [s for s in telemetry.global_tracer().finished
                    if s.name == "crush.host_residue"]
    assert root_span.attrs["lanes"] == nf


def test_up_derive_span():
    """``pg_to_up_bulk`` records the up-set derivation after stage 1
    as one ``crush.up_derive`` span."""
    from ceph_tpu.cluster.topology import EC_POOL, ClusterSpec, build_cluster

    prev = telemetry.set_global_metrics(MetricsRegistry())
    try:
        om = build_cluster(ClusterSpec.sized(16, seed=1, ec_k=2, ec_m=1,
                                             ec_pg_num=32,
                                             replicated_pg_num=8))
        om.pg_to_up_bulk(EC_POOL, engine="host")
        assert _hist("crush_up_derive_seconds")[0] == 1
    finally:
        telemetry.set_global_metrics(prev)
