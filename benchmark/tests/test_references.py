"""The plain references against the program at small sizes on the CPU:
two independent computations of the same semantics that agree (the
references themselves import nothing of the program)."""

import numpy as np
import pytest

from benchmark.reference import clay, crush, gf256, jerasure
from benchmark.drivers.osd_remap import plain_dump


def test_gf256_field():
    assert gf256.inv(2) == 142
    assert gf256.mul(0x80, 2) == 0x1D
    for a in (1, 2, 7, 142, 255):
        assert gf256.mul(a, gf256.inv(a)) == 1


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (6, 3), (2, 2), (10, 4)])
def test_reed_sol_van_matrix(k, m):
    from ceph_tpu.matrices.jerasure import reed_sol_vandermonde_coding_matrix
    assert np.array_equal(jerasure.coding_matrix(k, m),
                          np.asarray(reed_sol_vandermonde_coding_matrix(
                              k, m, 8)))


def _factory(plugin, profile):
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    return ErasureCodePluginRegistry.instance().factory(plugin, profile)


def test_rs_parity_matches_program():
    ec = _factory("jerasure", {"technique": "reed_sol_van", "k": "8",
                               "m": "3"})
    data = np.random.default_rng(1).integers(0, 256, (3, 8, 2048), np.uint8)
    assert np.array_equal(ec.encode_chunks_batch(data),
                          jerasure.parity(data, {"k": "8", "m": "3"}))


def test_clay_parity_matches_program():
    prof = {"k": "8", "m": "4", "d": "11"}
    ec = _factory("clay", prof)
    c = ec.get_chunk_size(8 * 64 * 16)
    data = np.random.default_rng(2).integers(0, 256, (2, 8, c), np.uint8)
    assert np.array_equal(ec.encode_chunks_batch(data),
                          clay.parity(data, prof))


def test_crush_matches_program_with_osds_out():
    from ceph_tpu.cluster.topology import EC_POOL, ClusterSpec, build_cluster
    om = build_cluster(ClusterSpec(seed=3, racks=4, hosts_per_rack=4,
                                   osds_per_host=4, device_classes=(),
                                   ec_k=8, ec_m=3, ec_pg_num=256,
                                   replicated_pg_num=32))
    m = crush.plain_map(plain_dump(om.crush))
    p = om.pools[EC_POOL]
    pool = {"pool_id": p.pool_id, "pg_num": p.pg_num, "pgp_num": p.pgp_num,
            "size": p.size, "crush_rule": p.crush_rule}
    om.osd_weight[5] = 0
    om.osd_weight[17] = 0x8000
    up, prim = crush.pg_to_up(m, pool, om.osd_weight)
    for ps in range(0, 256, 9):
        u, pr, _, _ = om.pg_to_up_acting_osds(EC_POOL, ps)
        assert up[ps].tolist() == u and prim[ps] == pr
    assert not (up == 5).any()
