"""The dense batcher's reused staging buffer (serve/batcher.py
``_Staging``): every launched stack is byte-identical to a fresh
zero-padded stack (payload rows, then zero pad rows) over any sequence
of occupancies, results never alias the buffer the next fire refills,
buckets of one ``(rows, chunk_size)`` share one buffer, and
``serve_pack_buffers`` counts one allocation per shape at warm-up and
a reuse for every fire after it."""

import numpy as np
import pytest

from ceph_tpu import telemetry
from ceph_tpu.codes.registry import ErasureCodePluginRegistry
from ceph_tpu.serve import ContinuousBatcher, EcRequest
from ceph_tpu.telemetry import MetricsRegistry
from ceph_tpu.utils.retry import FakeClock

RS21 = {"technique": "reed_sol_van", "k": "2", "m": "1"}
RS42 = {"technique": "reed_sol_van", "k": "4", "m": "2"}
LADDER = (1, 4, 16, 64)
STRIPE = 256
EXECUTORS = ("host", "device")


@pytest.fixture
def registry():
    prev = telemetry.set_global_metrics(MetricsRegistry())
    try:
        yield
    finally:
        telemetry.set_global_metrics(prev)


def _ec(profile):
    ec = ErasureCodePluginRegistry.instance().factory("jerasure",
                                                      dict(profile))
    ec.min_xla_bytes = float("inf")
    return ec


def _req(op, rng, profile=RS21, erased=(0,)):
    """One request; a decode/repair names every survivor of ``erased``
    as available."""
    ec = _ec(profile)
    n, k = ec.get_chunk_count(), ec.get_data_chunk_count()
    chunk = ec.get_chunk_size(STRIPE)
    if op == "encode":
        return EcRequest(op="encode", plugin="jerasure", profile=profile,
                         stripe_size=STRIPE,
                         payload=rng.integers(0, 256, (k, chunk),
                                              np.uint8))
    avail = tuple(i for i in range(n) if i not in erased)
    return EcRequest(op=op, plugin="jerasure", profile=profile,
                     stripe_size=STRIPE,
                     payload=rng.integers(0, 256, (len(avail), chunk),
                                          np.uint8),
                     available=avail, erased=tuple(erased))


def _reference(req):
    """The request's result computed alone, in numpy: per request, not
    through any batch stack."""
    ec = _ec(req.profile)
    one = req.payload[None]
    if req.op == "encode":
        return np.asarray(ec.encode_chunks_batch(one))[0]
    rec = np.asarray(ec.decode_chunks_batch(one, req.available,
                                            req.erased))[0]
    if req.op == "decode":
        return rec
    from ceph_tpu.serve.batcher import _host_repair
    _, parity = _host_repair(ec, one, req.available, req.erased)
    return rec, parity[0]


def _same(out, ref):
    if isinstance(ref, tuple):
        return all(np.array_equal(o, r) for o, r in zip(out, ref))
    return np.array_equal(out, ref)


def _batcher(executor, ladder=LADDER):
    return ContinuousBatcher(clock=FakeClock(), ladder=ladder,
                             executor=executor,
                             service_model=lambda b, r: 1e-4)


def _spy(batcher):
    """Copies of every stack the batcher launches."""
    launched = []
    real = batcher._launch

    def launch(b, stack, coef=None):
        launched.append(stack.copy())
        return real(b, stack, coef)
    batcher._launch = launch
    return launched


def _fire(batcher, reqs):
    """Fire ``reqs`` as one bucket's batch (split into top-rung slices
    past the top rung), whatever their occupancy."""
    b = batcher._bucket_for(reqs[0])
    b.requests.extend(reqs)
    return batcher._fire(b, "flush")


@pytest.mark.parametrize("executor", EXECUTORS)
def test_changing_occupancy_byte_identity(executor):
    """Occupancies 64, 5, 16, 1, then 40 on one bucket: each launched
    stack is the payloads then all-zero pad rows (rows a larger earlier
    fire left behind included), and each result equals the request's
    result computed alone."""
    rng = np.random.default_rng(11)
    batcher = _batcher(executor)
    batcher.warmup([_req("encode", rng)])
    launched = _spy(batcher)
    for n in (64, 5, 16, 1, 40):
        reqs = [_req("encode", rng) for _ in range(n)]
        results = _fire(batcher, reqs)
        stack = launched[-1]
        rung = min(r for r in LADDER if r >= n)
        assert stack.shape[0] == rung
        for i, r in enumerate(reqs):
            assert np.array_equal(stack[i], r.payload)
        assert not stack[n:].any(), f"stale pad rows at occupancy {n}"
        assert [res.request for res in results] == reqs
        for res in results:
            assert _same(res.output, _reference(res.request))


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("op", ("encode", "decode", "repair"))
def test_results_do_not_alias_the_staging_buffer(op, executor):
    """Fire 1's results, kept as handed back, are unchanged after fire
    2 refills the buffer, and none shares memory with it."""
    rng = np.random.default_rng(12)
    batcher = _batcher(executor, ladder=(1, 4))
    first = [_req(op, rng) for _ in range(3)]
    kept = _fire(batcher, first)
    snap = [tuple(np.copy(o) for o in r.output) if op == "repair"
            else np.copy(r.output) for r in kept]
    second = [_req(op, rng) for _ in range(4)]
    _fire(batcher, second)
    (staging,) = batcher._staging.values()
    for res, before in zip(kept, snap):
        outs = res.output if op == "repair" else (res.output,)
        for o in outs:
            assert not np.may_share_memory(o, staging.buf)
        assert _same(res.output, before)
        assert _same(res.output, _reference(res.request))


def test_buckets_of_one_shape_share_a_buffer():
    """Two decode buckets with the same row count and chunk size (one
    and two erasures: each a pattern-generic bucket of k survivor
    rows) share one buffer; a repair bucket of another row count gets
    its own; an oversized burst split into top-rung slices stays
    byte-identical, its short last slice padded with zeros."""
    rng = np.random.default_rng(13)
    batcher = _batcher("host", ladder=(1, 2, 4))
    launched = _spy(batcher)
    lost0 = [_req("decode", rng, RS42, erased=(0,)) for _ in range(3)]
    lost05 = [_req("decode", rng, RS42, erased=(0, 5))
              for _ in range(2)]
    rep = [_req("repair", rng, RS42, erased=(5,)) for _ in range(11)]
    out = _fire(batcher, lost0) + _fire(batcher, lost05)
    b0, b05 = (batcher._bucket_for(r[0]) for r in (lost0, lost05))
    assert b0 is not b05 and b0.rows == b05.rows == 4
    assert len(batcher._staging) == 1
    out += _fire(batcher, rep)
    assert set(batcher._staging) == {(4, b0.chunk_size),
                                     (5, b0.chunk_size)}
    assert [d["occupancy"] for d in batcher.dispatch_log] == [3, 2, 4, 4,
                                                              3]
    assert [s.shape[0] for s in launched] == [4, 2, 4, 4, 4]
    assert not launched[0][3].any() and not launched[-1][3].any()
    assert [r.request for r in out] == lost0 + lost05 + rep
    for res in out:
        assert _same(res.output, _reference(res.request))


def _buffers():
    (body,) = telemetry.global_metrics().dump().values()
    return {k: v for k, v in body.items()
            if k.startswith("serve_pack_buffers{")}


def test_pack_buffer_counter(registry):
    """Warm-up allocates one buffer per shape (a second bucket of a
    warmed shape reuses it); every later fire counts ``reused``."""
    rng = np.random.default_rng(14)
    batcher = _batcher("host", ladder=(1, 4))
    warm = [_req("decode", rng, RS42, erased=(0,)),
            _req("decode", rng, RS42, erased=(0, 5)),
            _req("repair", rng, RS42, erased=(5,))]
    batcher.warmup(warm)
    assert _buffers() == {
        "serve_pack_buffers{op=decode,outcome=allocated}": 1,
        "serve_pack_buffers{op=decode,outcome=reused}": 1,
        "serve_pack_buffers{op=repair,outcome=allocated}": 1}
    _fire(batcher, [_req("decode", rng, RS42, erased=(0, 5))
                    for _ in range(6)])
    _fire(batcher, [_req("repair", rng, RS42, erased=(5,))
                    for _ in range(2)])
    assert batcher.dispatches == 3
    assert _buffers() == {
        "serve_pack_buffers{op=decode,outcome=allocated}": 1,
        "serve_pack_buffers{op=decode,outcome=reused}": 3,
        "serve_pack_buffers{op=repair,outcome=allocated}": 1,
        "serve_pack_buffers{op=repair,outcome=reused}": 1}
