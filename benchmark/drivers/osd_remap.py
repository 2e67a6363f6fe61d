"""OSD-out map changes: every epoch marks one OSD out (reweight 0) or
back in, alternating, and recomputes the up set and primary of every
PG of the pool with ``OSDMap.pg_to_up_bulk(pool, engine="bulk")``.

Traffic parameters (``benchmark/traffic/<mix>.json``):

- ``pool``: ``ec`` (the configuration's erasure-coded pool);
- ``flap_osds``: how many OSDs take turns going out; they are drawn
  evenly from the OSDs the pool's rule can place on whose CRUSH weight
  is the map's most common one, so every seed flaps OSDs of the same
  size, in its own order;
- ``check_epochs``: epochs compared with the plain reference, all of
  them up to this many, else a seeded sample of this many.

The cluster is the configuration's, built from its own ``map_seed``:
the deployment stays fixed, and ``--seed`` draws the order of events.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from ..harness import Check, Window, span, stage

IN = 0x10000


def plain_dump(crush) -> dict:
    """The CRUSH map as data (``ceph osd crush dump``'s content)."""
    from ceph_tpu.crush.types import BUCKET_ALG_NAMES
    return {
        "buckets": {b.id: {"type": b.type, "alg": BUCKET_ALG_NAMES[b.alg],
                           "items": list(b.items),
                           "weights": list(b.item_weights)}
                    for b in crush.buckets.values()},
        "max_devices": crush.max_devices,
        "rules": {rid: [tuple(s) for s in r.steps]
                  for rid, r in crush.rules.items()},
        "choose_total_tries": crush.tunables.choose_total_tries,
    }


class Cell:
    def __init__(self, spec, seed: int) -> None:
        self.spec = spec
        self.seed = seed
        self.cfg = spec.config
        self.tr = spec.traffic

    def setup(self) -> None:
        from ceph_tpu.cluster.topology import (EC_POOL, ClusterSpec,
                                               build_cluster)
        t0 = time.perf_counter()
        c = self.cfg["cluster"]
        pool = self.cfg["pool"]
        spec = ClusterSpec(
            seed=int(c["map_seed"]), racks=int(c["racks"]),
            hosts_per_rack=int(c["hosts_per_rack"]),
            osds_per_host=int(c["osds_per_host"]),
            weight_tiers=tuple(c["weight_tiers"]),
            device_classes=tuple(c["device_classes"]),
            replicated_pg_num=int(c["replicated_pg_num"]),
            ec_k=int(self.cfg["profile"]["k"]),
            ec_m=int(self.cfg["profile"]["m"]),
            ec_pg_num=int(pool["pg_num"]))
        self.om = build_cluster(spec)
        self.pool_id = EC_POOL
        self.pool = self.om.pools[EC_POOL]
        self.dump = plain_dump(self.om.crush)
        stage("map", t0)
        self.flap = self._flap_osds()
        rng = np.random.default_rng(self.seed)
        self.flap = [self.flap[i] for i in rng.permutation(len(self.flap))]
        # warm the shapes of both kinds of epoch: one OSD out, all in
        warm = self._candidates()[-1]
        for w in (0, IN):
            self.om.osd_weight[warm] = w
            self.om.pg_to_up_bulk(self.pool_id, engine="bulk")
            stage(f"warm-up epoch, osd.{warm} weight {w}", t0)

    def _candidates(self):
        """OSDs under the pool rule's take bucket, of the modal weight."""
        crush = self.om.crush
        take = next(a1 for op, a1, _ in crush.rules[self.pool.crush_rule].steps
                    if op == 1)
        leaves, stack = {}, [take]
        while stack:
            b = crush.buckets[stack.pop()]
            for it, w in zip(b.items, b.item_weights):
                if it < 0:
                    stack.append(it)
                else:
                    leaves[it] = w
        weights = np.array(list(leaves.values()))
        vals, counts = np.unique(weights, return_counts=True)
        modal = vals[np.argmax(counts)]
        return sorted(o for o, w in leaves.items() if w == modal)

    def _flap_osds(self):
        cand = self._candidates()
        n = int(self.tr["flap_osds"])
        step = len(cand) / n
        return [cand[int(i * step)] for i in range(n)]

    def window(self, seconds: float) -> Window:
        om = self.om
        self.epochs = []       # (weights, up, primary)
        t_start = time.perf_counter()
        t_end = t_start + seconds
        now = t_start
        i = 0
        # whole out/in pairs only, so that every window holds as many
        # epochs of each kind
        while now < t_end or i % 2:
            with span("bench.mark"):
                osd = self.flap[(i // 2) % len(self.flap)]
                om.osd_weight[osd] = 0 if i % 2 == 0 else IN
                weights = np.array(om.osd_weight, np.int64)
            with span("bench.place"):
                up, prim = om.pg_to_up_bulk(self.pool_id, engine="bulk")
            with span("bench.record"):
                self.epochs.append((weights, np.asarray(up),
                                    np.asarray(prim)))
            i += 1
            now = time.perf_counter()
        elapsed = now - t_start
        pgs = len(self.epochs) * self.pool.pg_num
        return Window(seconds=elapsed, attempted=pgs, failed=0,
                      e2e={"placement_pgs_per_s": pgs / elapsed},
                      layer={"epochs": len(self.epochs), "pgs": pgs})

    def release(self) -> None:
        self.om.invalidate_compiled()

    def check(self, reference=None):
        """PGs whose up set or up primary differs from the plain
        reference, over every epoch compared."""
        from ..reference import crush as ref
        pg_to_up = reference or ref.pg_to_up
        m = ref.plain_map(self.dump)
        pool = {"pool_id": self.pool.pool_id, "pg_num": self.pool.pg_num,
                "pgp_num": self.pool.pgp_num, "size": self.pool.size,
                "crush_rule": self.pool.crush_rule}
        n = int(self.tr["check_epochs"])
        idx = list(range(len(self.epochs)))
        if len(idx) > n:
            rng = np.random.default_rng([self.seed, 3])
            idx = sorted(rng.choice(idx, n, replace=False).tolist())
        wrong = 0
        for e in idx:
            weights, up, prim = self.epochs[e]
            want_up, want_prim = pg_to_up(m, pool, weights)
            bad = ((up.astype(np.int64) != want_up).any(axis=1)
                   | (prim.astype(np.int64) != want_prim))
            wrong += int(bad.sum())
        print(f"compared {len(idx)} of {len(self.epochs)} epochs, "
              f"{len(idx) * self.pool.pg_num} PGs", file=sys.stderr,
              flush=True)
        return [Check("pgs_wrong", wrong, 0)]
