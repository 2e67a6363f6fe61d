"""The isa cauchy reference and the placed-recovery cell on the CPU at a
tiny size: the reference's parity equals the program's isa encode, the
driver's lost-shard derivation equals a direct count over the map, and
the cell runs correct while its control and a planted fault do not."""

import time
from collections import Counter

import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import ec_recover_placed
from benchmark.reference import crush, gf256, isa
from benchmark.tests import controls
from benchmark.tests.conftest import SEED

CELL = "isa8_3.recover_2host"


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (10, 4)])
def test_parity_matches_program(k, m):
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    profile = {"technique": "cauchy", "k": str(k), "m": str(m)}
    ec = ErasureCodePluginRegistry.instance().factory("isa", profile)
    data = np.random.default_rng([SEED, k]).integers(0, 256, (3, k, 2048),
                                                     np.uint8)
    assert np.array_equal(ec.encode_chunks_batch(data),
                          isa.parity(data, profile))


def test_cauchy1_rows():
    """Row i of the coding block is 1 / (i ^ j): the first coding row
    of k=8 is the inverses of 8..15."""
    c = isa.coding_matrix(8, 3)
    assert c.shape == (3, 8)
    assert [int(v) for v in c[0]] == [gf256.inv(8 ^ j) for j in range(8)]
    assert all(gf256.mul(int(c[i, j]), (8 + i) ^ j) == 1
               for i in range(3) for j in range(8))


def _small(spec):
    """The cell's configuration on a 64-OSD map of 256 PGs, 32 KiB
    stripes, two of its hosts down."""
    cfg, tr = spec.config, spec.traffic
    cfg["cluster"].update(racks=4, hosts_per_rack=4, osds_per_host=4)
    cfg["pool"]["pg_num"] = 256
    cfg["stripe_width"] = 8 * 64 * 64
    om, _ = ec_recover_placed.cluster_map(cfg)
    hosts = sorted(b for b, bucket in om.crush.buckets.items()
                   if bucket.items and min(bucket.items) >= 0)
    tr.update(down_hosts=[hosts[0], hosts[-1]], object_bytes=4 * 8 * 64 * 64,
              outstanding_objects=2, check_every=1, check_ops=6)
    return om


def _direct(om, pool_id, down_hosts, lost):
    """Lost positions of each PG, counted from the up sets with every
    OSD up: the positions held by an OSD of a down host."""
    from benchmark.drivers.osd_remap import plain_dump
    pool = om.pools[pool_id]
    up, _ = crush.pg_to_up(
        crush.plain_map(plain_dump(om.crush)),
        {"pool_id": pool.pool_id, "pg_num": pool.pg_num,
         "pgp_num": pool.pgp_num, "size": pool.size,
         "crush_rule": pool.crush_rule},
        np.array(om.osd_weight, np.int64))
    down = {o for h in down_hosts for o in om.crush.buckets[h].items}
    out = []
    for row in up:
        pos = tuple(i for i, o in enumerate(row.tolist()) if o in down)
        if len(pos) == lost:
            out.append(pos)
    return out


@pytest.fixture
def small_spec():
    from ceph_tpu.cluster.topology import EC_POOL
    spec = harness.load_spec(CELL)
    om = _small(spec)
    want = _direct(om, EC_POOL, spec.traffic["down_hosts"],
                   spec.traffic["lost_shards"])
    spec.traffic["dataset_objects"] = len(want)
    return spec, om, want


def test_placed_losses_equal_a_direct_count(small_spec):
    spec, om, want = small_spec
    from ceph_tpu.cluster.topology import EC_POOL
    got = ec_recover_placed.placed_losses(
        om, EC_POOL, spec.traffic["down_hosts"], 2)
    assert got == want
    assert len(got) > 10 and len(Counter(got)) > 5
    assert all(len(p) == 2 for p in got)
    one = ec_recover_placed.placed_losses(
        om, EC_POOL, spec.traffic["down_hosts"], 1)
    assert one == _direct(om, EC_POOL, spec.traffic["down_hosts"], 1)


def test_setup_refuses_another_count(small_spec, cpu):
    spec, _, want = small_spec
    spec.traffic["dataset_objects"] = len(want) + 1
    with pytest.raises(harness.BenchError):
        harness.load_driver(spec).Cell(spec, SEED).setup()


def test_cell_runs_correct(small_spec, cpu):
    spec, _, want = small_spec
    res = harness.run(spec, SEED, 1.0, False, time.perf_counter(), cpu,
                      log=lambda msg: None)
    assert res["correct"], res["checks"]
    assert res["metrics"]["ec_gbps"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    assert sorted(cell.lost_ids) == sorted(want)


def test_control_and_fault_are_not_correct(small_spec, cpu, monkeypatch):
    from ceph_tpu.codes import engine
    spec, _, _ = small_spec
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    real = engine.serve_decode_generic

    def broken(*args, **kwargs):
        call = real(*args, **kwargs)

        def flipped(stack, coef):
            out = np.array(call(stack, coef))
            out.reshape(-1)[0] ^= 1
            return out
        return flipped
    monkeypatch.setattr(engine, "serve_decode_generic", broken)
    cell.window(1.0)
    cell.release()
    assert not all(c.ok for c in cell.check())
    monkeypatch.undo()
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    cell.window(1.0)
    cell.release()
    assert all(c.ok for c in cell.check())
    assert not all(c.ok for c in cell.check(
        reference=controls.for_cell(cell)))
