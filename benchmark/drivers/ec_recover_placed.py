"""Recovery reads of the PGs that downed hosts leave short of shards,
each object's lost positions as CRUSH placed them.

The served path, the loop and the comparison are ``ec_serve``'s
(``op: decode``); only the lost shards are derived differently.  The
configuration's cluster is built from its ``map_seed``, and the
benchmark's plain CRUSH (``benchmark/reference/crush.py``) gives every
PG's up set with the OSDs of ``down_hosts`` down (an EC pool keeps a
hole where a shard's OSD is down).  Each PG that lost exactly
``lost_shards`` shards holds one object, which has lost those
positions.  The map decides the objects and their patterns, the same
for every seed; the seed picks the data, which object is which PG, and
the order of the loop.

Traffic parameters, beside ``ec_serve``'s:

- ``down_hosts``: CRUSH ids of the host buckets that are down;
- ``lost_shards``: the shards a PG must have lost to be recovered here;
- ``dataset_objects``: how many PGs the map must give; set-up fails if
  it gives another number.
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import BenchError, stage
from . import ec_serve


def cluster_map(cfg: dict):
    """The configuration's cluster, built from its own ``map_seed``:
    (OSDMap, the EC pool's id)."""
    from ceph_tpu.cluster.topology import (EC_POOL, ClusterSpec,
                                           build_cluster)
    c = cfg["cluster"]
    spec = ClusterSpec(
        seed=int(c["map_seed"]), racks=int(c["racks"]),
        hosts_per_rack=int(c["hosts_per_rack"]),
        osds_per_host=int(c["osds_per_host"]),
        weight_tiers=tuple(c["weight_tiers"]),
        device_classes=tuple(c["device_classes"]),
        replicated_pg_num=int(c["replicated_pg_num"]),
        ec_k=int(cfg["profile"]["k"]), ec_m=int(cfg["profile"]["m"]),
        ec_pg_num=int(cfg["pool"]["pg_num"]))
    return build_cluster(spec), EC_POOL


def placed_losses(om, pool_id: int, down_hosts, lost: int):
    """The lost positions of every PG that lost exactly ``lost``
    shards with the OSDs of ``down_hosts`` down, in PG order."""
    from ..reference import crush as ref
    from .osd_remap import plain_dump
    pool = om.pools[pool_id]
    weights = np.array(om.osd_weight, np.int64)
    alive = np.ones(len(weights), bool)
    for h in down_hosts:
        if h not in om.crush.buckets:
            raise BenchError(f"no host bucket {h} in the map")
        alive[[o for o in om.crush.buckets[h].items if o >= 0]] = False
    up, _ = ref.pg_to_up(
        ref.plain_map(plain_dump(om.crush)),
        {"pool_id": pool.pool_id, "pg_num": pool.pg_num,
         "pgp_num": pool.pgp_num, "size": pool.size,
         "crush_rule": pool.crush_rule}, weights, alive)
    holes = up == ref.NONE
    return [tuple(np.nonzero(h)[0].tolist()) for h in holes
            if h.sum() == lost]


class Cell(ec_serve.Cell):
    def setup(self) -> None:
        t0 = time.perf_counter()
        om, pool_id = cluster_map(self.spec.config)
        self.placed = placed_losses(om, pool_id,
                                    self.spec.traffic["down_hosts"],
                                    self.lost)
        if len(self.placed) != self.dataset:
            raise BenchError(
                f"the map gives {len(self.placed)} PGs that lost "
                f"{self.lost} shards; the traffic expects {self.dataset}")
        stage(f"placement: {len(self.placed)} PGs, "
              f"{len(set(self.placed))} patterns", t0)
        super().setup()

    def _store(self, ec, rng) -> None:
        """``ec_serve``'s store, with the placed losses dealt to the
        objects in a seeded order."""
        d, s, k, c = self.data.shape
        parity = np.asarray(ec.encode_chunks_batch(
            self.data.reshape(d * s, k, c))).reshape(d, s, -1, c)
        full = np.concatenate([self.data, parity], axis=2)
        del parity
        self.lost_ids = [self.placed[i] for i in rng.permutation(d)]
        self.avail = []
        helpers = None
        for obj, lost in enumerate(self.lost_ids):
            rest = set(range(self.n)) - set(lost)
            avail = tuple(sorted(ec.minimum_to_decode(set(lost), rest)))
            self.avail.append(avail)
            if helpers is None:
                helpers = np.empty((d, s, len(avail), c), np.uint8)
            helpers[obj] = full[obj][:, list(avail)]
        self.helpers = helpers
