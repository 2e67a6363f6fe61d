"""Multi-chip scaling bench (VERDICT r04 Next#7).

Measures the sharded flagship paths at 1/2/4/8 mesh devices:

- bulk CRUSH sweep (`parallel/sharded_crush.py`), mappings/s — pure
  data parallelism over the pg axis;
- sharded erasure encode (`parallel/sharded_codes.py`, dp stripe
  sharding), input GB/s.

Each device count runs in a SUBPROCESS because
`xla_force_host_platform_device_count` is frozen at backend init.  The
per-device work partition is reported from the OUTPUT sharding itself
(addressable-shard lane counts), so the table shows both wall-clock
and the 1/N division of work.

Reading wall-clock on virtual CPU devices: XLA gives each host device
its own threadpool, so wall-clock speedup tracks PHYSICAL cores.  On a
single-core host (this image: nproc == 1) the virtual devices
time-slice one core and wall-clock stays flat — the honest evidence
there is the shard partition plus flat-not-degrading wall time (the
collective/partition machinery adds no superlinear overhead), with
chip wall-clock scaling left to real multi-chip hardware.  Reference:
SURVEY.md §2.3 parallelism table (CrushTester fan-out / striped EC).

Usage:  python tools/sharded_bench.py            # parent: sweeps 1,2,4,8
        python tools/sharded_bench.py --child N  # one measurement
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 200_000
ENC_BATCH, ENC_K, ENC_CHUNK, ENC_LOOP = 32, 8, 128 * 1024, 4


def child(n: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.device_count() >= n, (jax.device_count(), n)
    devs = np.array(jax.devices()[:n])

    from ceph_tpu.crush import CrushBuilder
    from ceph_tpu.matrices.jerasure import (
        reed_sol_vandermonde_coding_matrix)
    from ceph_tpu.parallel.sharded_codes import sharded_encode
    from ceph_tpu.parallel.sharded_crush import sharded_bulk_do_rule

    # -- bulk CRUSH sweep, dp over the pg axis ---------------------------
    b = CrushBuilder()
    root = b.build_two_level(8, 4)
    b.add_simple_rule(0, root, "host", firstn=True)
    cmesh = Mesh(devs, ("x",))
    xs = np.arange(LANES)
    sharded_bulk_do_rule(cmesh, b.map, 0, xs, 3)          # warm/compile
    t0 = time.perf_counter()
    out, cnt = sharded_bulk_do_rule(cmesh, b.map, 0, xs, 3)
    crush_dt = time.perf_counter() - t0
    assert out.shape == (LANES, 3)

    # -- sharded encode, dp over the stripe axis -------------------------
    emesh = Mesh(devs.reshape(n, 1), ("stripe", "chunk"))
    matrix = reed_sol_vandermonde_coding_matrix(ENC_K, 3, 8)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (ENC_BATCH * n, ENC_K, ENC_CHUNK),
                        dtype=np.uint8)
    darr = jax.device_put(
        jnp.asarray(data),
        NamedSharding(emesh, P("stripe", "chunk", None)))
    parity = sharded_encode(emesh, darr, matrix)          # warm/compile
    np.asarray(parity.ravel()[:4])
    t0 = time.perf_counter()
    for _ in range(ENC_LOOP):
        parity = sharded_encode(emesh, darr, matrix)
    np.asarray(parity.ravel()[:4])
    enc_dt = time.perf_counter() - t0
    # per-device partition evidence from the output sharding itself
    shard_rows = sorted(s.data.shape[0] for s in parity.addressable_shards)
    return {
        "n_devices": n,
        "crush_mappings_per_s": round(LANES / crush_dt),
        "crush_seconds": round(crush_dt, 3),
        "encode_gbps": round(data.nbytes * ENC_LOOP / enc_dt / 1e9, 3),
        "encode_stripes_per_device": shard_rows,
        "devices": [str(d) for d in devs],
    }


def main() -> int:
    if "--child" in sys.argv:
        n = int(sys.argv[sys.argv.index("--child") + 1])
        print(json.dumps(child(n)))
        return 0
    rows = []
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={n}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags)
        else:
            flags = f"{flags} {flag}".strip()
        env["XLA_FLAGS"] = flags
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child", str(n)],
                capture_output=True, text=True, env=env, timeout=1200)
        except subprocess.TimeoutExpired:
            # a stuck child (XLA compile stall on an odd host) must
            # not abort the sweep: same error-row-and-continue path as
            # a nonzero exit
            print(json.dumps({"n_devices": n,
                              "error": ["timeout after 1200s"]}))
            continue
        if r.returncode != 0:
            print(json.dumps({"n_devices": n, "error":
                              r.stderr.strip().splitlines()[-1:]}))
            continue
        row = json.loads(r.stdout.strip().splitlines()[-1])
        rows.append(row)
        print(json.dumps(row))
    if len(rows) > 1:
        base = rows[0]
        summary = {
            "metric": "sharded_scaling",
            "physical_cores": os.cpu_count(),
            # explicit baseline: a failed N=1 child must not silently
            # rebaseline the "speedup" to the next device count
            "baseline_devices": base["n_devices"],
            "max_devices": rows[-1]["n_devices"],
            "crush_speedup_at_max": round(
                rows[-1]["crush_mappings_per_s"]
                / base["crush_mappings_per_s"], 2),
            "encode_speedup_at_max": round(
                rows[-1]["encode_gbps"] / base["encode_gbps"], 2),
        }
        print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
