"""The pattern-generic decode of w=8 matrix codes: the operand-coefficient
GF(2^8) kernel (ops/pallas_gf.py, Pallas interpret mode and its XLA and
numpy tiers), the engine's generic decode program and coefficient table
(codes/engine.py), and the batcher's one bucket per erasure count
(serve/batcher.py).

Truth is the plain references of the benchmark (benchmark/reference/
isa.py, jerasure.py), which import nothing of the program: every stripe
is encoded by the reference, and every rebuilt chunk must equal the
reference's bytes.  Every batch mixes all 11 single-shard and all 55
two-shard loss patterns of an 8+3 code."""

import itertools
import os
import sys

import jax
import numpy as np
import pytest

from ceph_tpu import telemetry
from ceph_tpu.codes import engine
from ceph_tpu.codes.engine import (PatternCache, decode_table,
                                   pattern_generic_decode,
                                   serve_decode_generic,
                                   set_global_pattern_cache)
from ceph_tpu.codes.registry import ErasureCodePluginRegistry
from ceph_tpu.ops import pallas_gf
from ceph_tpu.serve import ContinuousBatcher, EcRequest
from ceph_tpu.telemetry import MetricsRegistry
from ceph_tpu.utils.retry import FakeClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import isa as ref_isa  # noqa: E402
from benchmark.reference import jerasure as ref_jerasure  # noqa: E402

CHUNK = 4096
N = 11
CODES = {
    "isa_cauchy": ("isa", {"technique": "cauchy", "k": "8", "m": "3"},
                   ref_isa),
    "jerasure_rs_van": ("jerasure",
                        {"technique": "reed_sol_van", "k": "8", "m": "3"},
                        ref_jerasure),
}
SINGLE = [(e,) for e in range(N)]
DOUBLE = list(itertools.combinations(range(N), 2))


@pytest.fixture
def registry():
    prev = telemetry.set_global_metrics(MetricsRegistry())
    try:
        yield
    finally:
        telemetry.set_global_metrics(prev)


@pytest.fixture
def fresh_cache():
    prev = set_global_pattern_cache(PatternCache())
    try:
        yield engine.global_pattern_cache()
    finally:
        set_global_pattern_cache(prev)


def _ec(name):
    plugin, profile, _ = CODES[name]
    return ErasureCodePluginRegistry.instance().factory(plugin,
                                                        dict(profile))


def _stripes(name, patterns, seed):
    """One reference-encoded stripe per pattern: (full (P, 11, C), the
    survivors each pattern's decode reads (P, 8, C) and their ids)."""
    _, profile, ref = CODES[name]
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (len(patterns), 8, CHUNK), np.uint8)
    full = np.concatenate([data, ref.parity(data, profile)], axis=1)
    avail = [tuple(i for i in range(N) if i not in p)[:8]
             for p in patterns]
    surv = np.stack([full[i, list(a)] for i, a in enumerate(avail)])
    return full, surv, avail


def _mixed_operand(ec, patterns, avail):
    """The (P, 2, 8) coefficient operand of a batch mixing one- and
    two-shard patterns: a single loss's rows are its decode row and a
    zero row."""
    coef = np.zeros((len(patterns), 2, 8), np.uint8)
    for i, (p, a) in enumerate(zip(patterns, avail)):
        coef[i, :len(p)] = ec.decode_rows(a, p)
    return coef


@pytest.mark.parametrize("name", sorted(CODES))
def test_operand_kernel_rebuilds_every_pattern(name):
    """All 66 patterns in ONE kernel call of the Pallas kernel
    (interpret mode), its XLA tier and its numpy twin: every rebuilt
    chunk equals the reference's, every unused row is zero."""
    ec = _ec(name)
    patterns = SINGLE + DOUBLE
    full, surv, avail = _stripes(name, patterns, seed=1)
    coef = _mixed_operand(ec, patterns, avail)
    tiers = {
        "pallas": pallas_gf.apply_matrix_operand_pallas(
            jax.device_put(surv), jax.device_put(coef), True),
        "xla": pallas_gf.apply_matrix_operand_xla(
            jax.device_put(surv), jax.device_put(coef)),
        "numpy": pallas_gf.host_apply_operand(surv, coef),
    }
    for tier, out in tiers.items():
        out = np.asarray(out)
        for i, p in enumerate(patterns):
            assert np.array_equal(out[i, :len(p)], full[i, list(p)]), \
                (tier, p)
            assert not out[i, len(p):].any(), (tier, p)


def test_operand_kernel_equals_the_static_kernel():
    """A batch that repeats one matrix gives the static kernel's bytes,
    for a 4 KiB chunk and one off the sublane tile (pad rows)."""
    rng = np.random.default_rng(2)
    for c in (4096, 128 * 40):
        x = rng.integers(0, 256, (3, 8, c), np.uint8)
        m = rng.integers(0, 256, (3, 8), np.uint8)
        static = tuple(tuple(int(v) for v in row) for row in m)
        coef = np.broadcast_to(m, (3, 3, 8)).copy()
        want = np.asarray(pallas_gf.apply_matrix_pallas(
            jax.device_put(x), static, True))
        got = np.asarray(pallas_gf.apply_matrix_operand_pallas(
            jax.device_put(x), jax.device_put(coef), True))
        assert np.array_equal(got, want)


@pytest.mark.parametrize("name", sorted(CODES))
def test_engine_generic_decode(name, fresh_cache):
    """The engine's generic decode program, fed by decode_table: the
    55 two-shard patterns in one call and the 11 single-shard ones in
    another, each against the references."""
    ec = _ec(name)
    assert pattern_generic_decode(ec)
    for patterns, seed in ((DOUBLE, 3), (SINGLE, 4)):
        full, surv, avail = _stripes(name, patterns, seed)
        pats = list(zip(avail, patterns))
        rung = len(patterns) + 3
        coef = decode_table(ec, pats, list(range(len(pats))), rung)
        assert coef.shape == (rung, len(patterns[0]), 8)
        assert not coef[len(pats):].any()
        stack = np.zeros((rung, 8, CHUNK), np.uint8)
        stack[:len(pats)] = surv
        out = np.asarray(serve_decode_generic(ec, len(patterns[0]))(
            stack, coef))
        for i, p in enumerate(patterns):
            assert np.array_equal(out[i], full[i, list(p)]), p
        assert not out[len(pats):].any()
    # one program per erasure count, whatever the patterns
    keys = [k for k in fresh_cache._entries
            if k[2] == "serve-decode-generic"]
    assert len(keys) == 2


def test_decode_table_counts_hits_and_misses(registry, fresh_cache):
    ec = _ec("isa_cauchy")
    avail = [tuple(i for i in range(N) if i not in p)[:8]
             for p in DOUBLE[:3]]
    pats = list(zip(avail, DOUBLE[:3]))
    first = decode_table(ec, pats, [2, 0, -1, 1], 6)
    again = decode_table(ec, pats[:2], [1, 1, 0], 4)
    assert np.array_equal(first[0], ec.decode_rows(*pats[2]))
    assert not first[2].any() and not first[4:].any()
    assert np.array_equal(again[:2], np.stack([first[3]] * 2))
    assert np.array_equal(again[2], first[1])
    series = _series("engine_decode_matrix")
    assert series == {"engine_decode_matrix{outcome=miss}": 3,
                      "engine_decode_matrix{outcome=hit}": 2}
    assert _hist("engine_decode_table_seconds")[0] == 2


def test_shec_keeps_its_per_pattern_decode():
    """Codes whose decode is not the plain matrix decode keep their
    per-pattern programs and buckets."""
    reg = ErasureCodePluginRegistry.instance()
    shec = reg.factory("shec", {"k": "4", "m": "3", "c": "2"})
    clay = reg.factory("clay", {"k": "4", "m": "2"})
    cauchy = reg.factory("jerasure", {"technique": "cauchy_good",
                                      "k": "4", "m": "2"})
    wide = reg.factory("jerasure", {"technique": "reed_sol_van",
                                    "k": "4", "m": "2", "w": "16"})
    assert not any(pattern_generic_decode(ec)
                   for ec in (shec, clay, cauchy, wide))
    b = ContinuousBatcher(clock=FakeClock(), executor="host")
    reqs = [EcRequest(op="decode", plugin="shec",
                      profile={"k": "4", "m": "3", "c": "2"},
                      stripe_size=4096,
                      payload=np.zeros((6, 1024), np.uint8),
                      available=tuple(i for i in range(7) if i != e),
                      erased=(e,), deadline=1.0) for e in (0, 1)]
    assert b.bucket_key(reqs[0]) != b.bucket_key(reqs[1])


# ----------------------------------------------------------------------
# the batcher: one bucket, one program per rung, for all 55 patterns


def _series(name):
    (body,) = telemetry.global_metrics().dump().values()
    return {k: v for k, v in body.items()
            if k == name or k.startswith(name + "{")}


def _hist(name):
    c = s = 0
    for v in _series(name).values():
        c += v["count"]
        s += v["sum"]
    return c, s


def _requests(name, patterns, seed):
    plugin, profile, _ = CODES[name]
    full, _, _ = _stripes(name, patterns, seed)
    reqs, want = [], {}
    for i, p in enumerate(patterns):
        # every survivor named; the decode reads the first k of them
        avail = tuple(j for j in range(N) if j not in p)
        r = EcRequest(op="decode", plugin=plugin, profile=dict(profile),
                      stripe_size=8 * CHUNK, payload=full[i, list(avail)],
                      available=avail, erased=p, deadline=1.0)
        reqs.append(r)
        want[r.req_id] = full[i, list(p)]
    return reqs, want


@pytest.mark.parametrize("executor", ("host", "device"))
@pytest.mark.parametrize("name", sorted(CODES))
def test_batcher_one_bucket_for_55_patterns(name, executor, registry,
                                            fresh_cache):
    """55 two-shard patterns warm ONE bucket (one cached program per
    rung), fire at the full top rung twice, and demux every request
    its own chunks; the fires observe 55 patterns each, the first
    fire's decode rows miss and the second's hit."""
    ladder = (1, 5, 11, 55)
    b = ContinuousBatcher(clock=FakeClock(), executor=executor,
                          ladder=ladder, service_model=lambda u, r: 1e-4)
    reqs, want = _requests(name, DOUBLE, seed=5)
    assert len({b.bucket_key(r) for r in reqs}) == 1
    assert b.warmup(reqs) == len(ladder)
    assert len(b._buckets) == 1
    assert b.cached_program_count() == len(ladder)
    more, more_want = _requests(name, DOUBLE[::-1], seed=6)
    want.update(more_want)
    results = b.admit(reqs) + b.admit(more)
    assert b.pending() == 0
    assert [(d["occupancy"], d["rung"]) for d in b.dispatch_log] == \
        [(55, 55), (55, 55)]
    assert _series("serve_dispatches") == {
        "serve_dispatches{op=decode,reason=full}": 2}
    assert b.cached_program_count() == len(ladder)
    assert [r.request for r in results] == reqs + more
    for res in results:
        assert np.array_equal(res.output, want[res.request.req_id])
    assert _hist("serve_dispatch_patterns") == (2, 110)
    assert _series("engine_decode_matrix") == {
        "engine_decode_matrix{outcome=miss}": 55,
        "engine_decode_matrix{outcome=hit}": 55}
    if executor == "device":
        keys = [k for k in fresh_cache._entries
                if k[2].startswith("serve-decode")]
        assert len(keys) == 1 and keys[0][2] == "serve-decode-generic"


@pytest.mark.parametrize("name", sorted(CODES))
def test_batcher_mixed_erasure_counts(name, registry):
    """All 66 patterns admitted together: one bucket per erasure
    count, each request rebuilt byte for byte."""
    b = ContinuousBatcher(clock=FakeClock(), executor="device",
                          ladder=(1, 4, 16, 64))
    reqs, want = _requests(name, SINGLE + DOUBLE, seed=7)
    results = b.admit(reqs) + b.flush()
    assert len(b._buckets) == 2
    assert sorted((d["occupancy"], d["rung"]) for d in b.dispatch_log) \
        == [(11, 16), (55, 64)]
    assert len(results) == len(reqs)
    for res in results:
        assert np.array_equal(res.output, want[res.request.req_id])
    assert _hist("serve_dispatch_patterns") == (2, 66)


@pytest.mark.parametrize("executor", ("host", "device"))
def test_paged_generic_queue(executor, registry):
    """The paged path takes the same program: one queue for the 55
    patterns, a page a mini-stripe, dead pages' rows zero."""
    b = ContinuousBatcher(clock=FakeClock(), executor=executor,
                          paged=True, page_size=1024, pool_pages=256,
                          service_model=lambda u, r: 1e-4)
    reqs, want = _requests("isa_cauchy", DOUBLE, seed=8)
    assert b.warmup(reqs[:1]) == 1
    results = b.admit(reqs) + b.flush()
    assert len(b._queues) == 1 and b.cached_program_count() == 1
    assert len(results) == len(reqs)
    for res in results:
        assert np.array_equal(res.output, want[res.request.req_id])
    count, total = _hist("serve_dispatch_patterns")
    assert total == 55 and count == b.dispatches


def test_sharded_generic_decode(registry):
    """On a data plane the stack and the coefficients shard alike,
    byte-identical to the single-device program (6 stripes on 8
    devices: pad and mask)."""
    from ceph_tpu.parallel.plane import mesh_plane
    ec = _ec("isa_cauchy")
    full, surv, avail = _stripes("isa_cauchy", DOUBLE[:6], seed=9)
    coef = decode_table(ec, list(zip(avail, DOUBLE[:6])), range(6), 6)
    single = np.asarray(serve_decode_generic(ec, 2, mesh=False)(
        surv, coef))
    with mesh_plane() as p:
        assert p is not None and p.n_devices > 1
        sharded = np.asarray(serve_decode_generic(ec, 2)(
            jax.device_put(surv), jax.device_put(coef)))
    assert np.array_equal(sharded, single)
    for i, p in enumerate(DOUBLE[:6]):
        assert np.array_equal(single[i], full[i, list(p)])
