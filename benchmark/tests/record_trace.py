"""Record a small device trace of the benchmark's programs on a TPU.

Runs one RS 8+3 encode dispatch and one clay 8+4 d=11 decode dispatch
through the serve batcher, and one bulk-CRUSH sweep of a small EC
pool, under the JAX profiler with the benchmark's host spans around
them.  The trace lands in ``--out`` and is what
``benchmark/tests/data/`` keeps for the trace-reduction test.  It also
prints, for a look by hand, every plane, line and the first event
names of each line.

    python benchmark/tests/record_trace.py --out <dir>
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--stripes", type=int, default=4)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from ceph_tpu.utils.compile_cache import initialize_compile_cache
    initialize_compile_cache()
    from ceph_tpu.serve.batcher import ContinuousBatcher
    from ceph_tpu.serve.queue import AdmissionQueue, EcRequest
    from ceph_tpu.cluster.topology import EC_POOL, ClusterSpec, build_cluster

    rng = np.random.default_rng(1)
    b = ContinuousBatcher()
    rs = {"technique": "reed_sol_van", "k": "8", "m": "3"}
    enc = [EcRequest(op="encode", plugin="jerasure", profile=rs,
                     stripe_size=1 << 20,
                     payload=rng.integers(0, 256, (8, 131072), np.uint8))
           for _ in range(args.stripes)]
    clay = {"k": "8", "m": "4", "d": "11"}
    avail = tuple(range(1, 12))
    dec = [EcRequest(op="decode", plugin="clay", profile=clay,
                     stripe_size=1 << 20, available=avail, erased=(0,),
                     payload=rng.integers(0, 256, (11, 131072), np.uint8))
           for _ in range(args.stripes)]
    t0 = time.perf_counter()
    b.warmup(enc[:1] + dec[:1])
    print(json.dumps({"warmup_s": time.perf_counter() - t0}), flush=True)
    om = build_cluster(ClusterSpec.sized(64, seed=1, ec_k=8, ec_m=3,
                                         ec_pg_num=256,
                                         replicated_pg_num=64))
    om.pg_to_up_bulk(EC_POOL, engine="bulk")
    q = AdmissionQueue()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(args.out, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            for reqs in (enc, dec):
                with jax.profiler.TraceAnnotation("bench.submit"):
                    for r in reqs:
                        q.submit(r)
                with jax.profiler.TraceAnnotation("bench.poll"):
                    out = b.poll(q) + b.flush()
                assert len(out) == len(reqs)
            with jax.profiler.TraceAnnotation("bench.epoch"):
                om.osd_weight[3] = 0
                om.pg_to_up_bulk(EC_POOL, engine="bulk")
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    print(json.dumps({"trace": path, "bytes": os.path.getsize(path)}))
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            ev = list(line.events)
            names = []
            for e in ev:
                if e.name not in names:
                    names.append(e.name)
            stats = {}
            if ev:
                try:
                    stats = {k: str(v)[:80] for k, v in ev[0].stats}
                except Exception as exc:  # noqa: BLE001 - a look by hand
                    stats = {"error": repr(exc)}
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": len(ev), "names": names[:25],
                              "first_stats": stats,
                              "first_start_ns": ev[0].start_ns if ev else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
