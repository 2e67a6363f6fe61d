#!/usr/bin/env python3
"""Bring-up smoke: the EC + bulk-CRUSH main path, once, on a TPU.

One process drives the entry points a Ceph operator's stack calls —
the erasure-code plugin API (encode, minimum_to_decode, decode) and
the OSDMap's bulk PG→OSD mapping — at a realistic size, and checks
every result byte for byte against the host ground truth
(``regionops`` / the erased data / the scalar CRUSH mapper).  The
encode runs once more after the placement phase, with JAX's 32-bit
defaults, as a process that places and then encodes would.

Each phase prints one informational JSON line (phase, engine tiers,
bytes, seconds, compiles).  Times are set-up evidence, not benchmark
results.  The LAST line is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when every phase passed.  Without a TPU (or with
``CEPH_TPU_ENGINE`` / ``CEPH_TPU_MESH`` set, which could force a
tier) the script exits nonzero before any phase runs.

``--chips 4`` runs only the multi-chip phases: the RS encode batch
through the data plane over 4 devices and the sharded bulk CRUSH
sweep, each compared with the one-device result of the same process.

The phase functions take their sizes as arguments; tests run them at
tiny size on the CPU (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

FORCING_VARS = ("CEPH_TPU_ENGINE", "CEPH_TPU_MESH")
# tiers select_matrix_engine may pick for a device dispatch; "numpy"
# (the host ground truth) never counts as a device run
DEVICE_TIERS = frozenset({"pallas", "xor", "mxu", "xla", "mesh"})
RS_PROFILE = {"technique": "reed_sol_van", "k": "8", "m": "3"}
SHEC_PROFILE = {"k": "6", "m": "3", "c": "2"}
CLAY_PROFILE = {"k": "8", "m": "4", "d": "11"}


class SmokeFailure(RuntimeError):
    """A phase's result is wrong, or the path left the device."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ----------------------------------------------------------------------
# observation helpers (telemetry the library already keeps)

def _counters() -> dict:
    from ceph_tpu.telemetry import global_metrics
    (body,) = global_metrics().dump().values()
    return {k: v for k, v in body.items() if isinstance(v, int)}


def _delta(before: dict, prefix: str) -> dict:
    after = _counters()
    return {k: after[k] - before.get(k, 0) for k in after
            if k.startswith(prefix) and after[k] != before.get(k, 0)}


def _tiers(before: dict) -> dict:
    """engine tier -> device dispatches since ``before`` (the
    ``ops_apply_matrix*_calls{engine=...}`` series
    select_matrix_engine's choice is recorded under)."""
    out: dict = {}
    for series, n in _delta(before, "ops_apply_matrix").items():
        if not series.split("{")[0].endswith("_calls"):
            continue
        eng = re.search(r"engine=(\w+)", series).group(1)
        out[eng] = out.get(eng, 0) + n
    return out


def _compiles(before: dict) -> int:
    return _counters().get("jax_backend_compiles", 0) \
        - before.get("jax_backend_compiles", 0)


def _check_tiers(tiers: dict, what: str) -> None:
    check(bool(tiers), f"{what}: no device dispatch recorded")
    bad = set(tiers) - DEVICE_TIERS
    check(not bad, f"{what}: ran on non-device tier(s) {sorted(bad)}")


def _check_on_device(arr, device, what: str) -> None:
    devs = arr.devices()
    check(devs == {device},
          f"{what}: result lives on {sorted(map(str, devs))}, "
          f"not {device}")


def watch_pallas_calls() -> list:
    """Record the ``interpret`` flag of every pallas_call built from
    here on (all kernels are built through ``pl.pallas_call`` in
    ops/pallas_gf.py, at trace time, so every program this process
    traces passes through)."""
    from jax.experimental import pallas as pl
    seen: list = []
    orig = pl.pallas_call

    def recording(*args, **kwargs):
        seen.append(bool(kwargs.get("interpret", False)))
        return orig(*args, **kwargs)

    pl.pallas_call = recording
    return seen


# ----------------------------------------------------------------------
# phases

def require_device(platform: str = "tpu", count: int = 1) -> dict:
    """The device phase: refuse forcing knobs, require ``count``
    devices of ``platform``.  Returns {platform, kind, count}."""
    forced = [v for v in FORCING_VARS if os.environ.get(v)]
    check(not forced, f"refusing to run with {forced} set: the smoke "
                      f"must see the tiers the library picks itself")
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == platform,
          f"no {platform} device: jax reports {dev}")
    check(dev["count"] >= count,
          f"need {count} {platform} devices, jax reports {dev['count']}")
    emit("device", **dev)
    return dev


def _factory(plugin: str, profile: dict):
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    return ErasureCodePluginRegistry.instance().factory(plugin,
                                                        dict(profile))


def encode_phase(stripes: int, stripe_size: int, seed: int,
                 phase: str = "encode") -> dict:
    """RS k=8 m=3 through encode_chunks_batch (host arrays, the path
    ECBackend uses) and encode_chunks_jax (device-resident), both
    byte-identical to regionops.matrix_encode, with JAX's 32-bit
    defaults (crush/bulk.py's 64-bit scope must not leak here).
    Returns the batch (data + parity) for the decode phase."""
    import jax
    from ceph_tpu.ops import regionops

    check(not jax.config.jax_enable_x64,
          f"{phase}: 64-bit types are on for the EC path")

    ec = _factory("jerasure", RS_PROFILE)
    k, m = ec.get_data_chunk_count(), ec.get_coding_chunk_count()
    chunk = ec.get_chunk_size(stripe_size)
    data = np.random.default_rng(seed).integers(
        0, 256, size=(stripes, k, chunk), dtype=np.uint8)
    ref = regionops.matrix_encode(regionops.words_view(data, 8),
                                  ec.matrix, 8)

    c0 = _counters()
    t0 = time.perf_counter()
    parity = ec.encode_chunks_batch(data)
    t_batch = time.perf_counter() - t0
    check(np.array_equal(parity, ref),
          "encode_chunks_batch parity differs from regionops")
    tiers = _tiers(c0)
    _check_tiers(tiers, "encode_chunks_batch")

    dev = jax.devices()[0]
    resident = jax.device_put(data, dev)
    c1 = _counters()
    t0 = time.perf_counter()
    out = ec.encode_chunks_jax(resident).block_until_ready()
    t_jax = time.perf_counter() - t0
    _check_on_device(out, dev, "encode_chunks_jax")
    check(np.array_equal(np.asarray(out), ref),
          "encode_chunks_jax parity differs from regionops")
    jtiers = _tiers(c1)
    _check_tiers(jtiers, "encode_chunks_jax")
    emit(phase, codec="rs_k8_m3", tiers=tiers, jax_tiers=jtiers,
         bytes_in=int(data.nbytes), bytes_parity=int(parity.nbytes),
         batch_seconds=t_batch, jax_seconds=t_jax,
         compiles=_compiles(c0))
    return {"ec": ec, "chunks": np.concatenate([data, parity], axis=1)}


def _decode_check(name: str, ec, chunks: np.ndarray, erased: tuple
                  ) -> dict:
    """minimum_to_decode + decode_chunks_batch for one erasure
    pattern; the result must equal the erased chunks."""
    n = ec.get_chunk_count()
    avail = set(range(n)) - set(erased)
    reads = tuple(sorted(ec.minimum_to_decode(set(erased), avail)))
    c0 = _counters()
    t0 = time.perf_counter()
    out = ec.decode_chunks_batch(
        np.ascontiguousarray(chunks[:, list(reads), :]), reads, erased)
    secs = time.perf_counter() - t0
    check(np.array_equal(out, chunks[:, list(erased), :]),
          f"{name}: decode_chunks_batch differs from the erased chunks")
    tiers = _tiers(c0)
    _check_tiers(tiers, name)
    row = {"erased": list(erased), "reads": list(reads), "tiers": tiers,
           "bytes_in": int(chunks.shape[0] * len(reads)
                           * chunks.shape[2]),
           "seconds": secs, "compiles": _compiles(c0)}
    emit("decode", codec=name, **row)
    return row


def _encoded_batch(ec, stripes: int, stripe_size: int, seed: int
                   ) -> np.ndarray:
    k = ec.get_data_chunk_count()
    chunk = ec.get_chunk_size(stripe_size)
    data = np.random.default_rng(seed).integers(
        0, 256, size=(stripes, k, chunk), dtype=np.uint8)
    return np.concatenate([data, ec.encode_chunks_batch(data)], axis=1)


def decode_phase(rs: dict, stripes: int, shec_size: int,
                 clay_size: int, seed: int,
                 clay_profile: dict = CLAY_PROFILE) -> dict:
    """RS with 2 and 3 erasures on the encode batch; shec k=6 m=3 c=2
    single erasure and clay (k=8 m=4 d=11 unless the CPU test narrows
    it) single-chunk repair on their own seeded batches."""
    rows = {
        "rs_k8_m3_e2": _decode_check("rs_k8_m3_e2", rs["ec"],
                                     rs["chunks"], (1, 9)),
        "rs_k8_m3_e3": _decode_check("rs_k8_m3_e3", rs["ec"],
                                     rs["chunks"], (0, 4, 8)),
    }
    shec = _factory("shec", SHEC_PROFILE)
    rows["shec_k6_m3_c2_e1"] = _decode_check(
        "shec_k6_m3_c2_e1", shec,
        _encoded_batch(shec, stripes, shec_size, seed + 1), (0,))
    clay = _factory("clay", clay_profile)
    batch = _encoded_batch(clay, stripes, clay_size, seed + 2)
    check(clay.is_repair({0}, set(range(1, clay.get_chunk_count()))),
          "clay: chunk 0 loss is not a single-chunk repair")
    name = "clay_k{k}_m{m}_d{d}_e1".format(**clay_profile)
    rows[name] = _decode_check(name, clay, batch, (0,))
    return rows


POOLS = ("replicated_size3_rack", "ec_8_3_host_indep")


def crush_phase(n_osds: int, rep_pgs: int, ec_pgs: int,
                oracle_pgs: int, seed: int, engine: str = "bulk",
                pools: tuple = POOLS) -> dict:
    """pg_to_up_bulk over a replicated size-3 pool (rack domain) and
    an EC 8+3 pool (canonical indep rule over hosts) of a
    ClusterSpec.sized(n_osds) cluster, against the scalar
    pg_to_up_acting_osds oracle on every PG or on a seeded sample of
    ``oracle_pgs`` PGs per pool.  Returns {pool: up array}."""
    from ceph_tpu.cluster.topology import (EC_POOL, REPLICATED_POOL,
                                           ClusterSpec, build_cluster)
    from ceph_tpu.crush.types import CRUSH_ITEM_NONE

    spec = ClusterSpec.sized(n_osds, seed=seed, replicated_pg_num=rep_pgs,
                             ec_k=8, ec_m=3, ec_pg_num=ec_pgs)
    om = build_cluster(spec)
    rng = np.random.default_rng(seed)
    ups = {}
    for name, pid in zip(POOLS, (REPLICATED_POOL, EC_POOL)):
        if name not in pools:
            continue
        pool = om.pools[pid]
        c0 = _counters()
        t0 = time.perf_counter()
        up, prim = om.pg_to_up_bulk(pid, engine=engine)
        t_bulk = time.perf_counter() - t0
        if pool.pg_num <= oracle_pgs:
            pgs, oracle = np.arange(pool.pg_num), "all"
        else:
            pgs = np.sort(rng.choice(pool.pg_num, oracle_pgs,
                                     replace=False))
            oracle = "sample"
        t0 = time.perf_counter()
        for ps in pgs:
            u, p, _, _ = om.pg_to_up_acting_osds(pid, int(ps))
            want = (u + [CRUSH_ITEM_NONE] * pool.size)[:pool.size]
            check(up[ps].tolist() == want and int(prim[ps]) == p,
                  f"{name}: pg {ps} bulk {up[ps].tolist()}/{prim[ps]} "
                  f"!= oracle {want}/{p}")
        t_oracle = time.perf_counter() - t0
        emit("crush", pool=name, engine=engine, osds=spec.n_osds,
             pgs=pool.pg_num, size=pool.size, oracle=oracle,
             oracle_pgs=int(len(pgs)), bulk_seconds=t_bulk,
             oracle_seconds=t_oracle, compiles=_compiles(c0),
             mesh_dispatches=_delta(c0, "engine_mesh_dispatches"))
        ups[name] = up
    return ups


def no_fallback_phase(platform: str, engine: str,
                      pallas_calls: list) -> dict:
    """Nothing left the device: the fallback policy's only transition
    is (platform, engine) and unforced, the supervisor never demoted,
    quarantined or completed on the host, device EC calls happened,
    no plane degraded, and no pallas_call was built in interpret
    mode."""
    from ceph_tpu.ops.fallback import global_policy
    from ceph_tpu.ops.supervisor import global_supervisor
    from ceph_tpu.utils.perf import global_perf

    counters = _counters()
    transitions = {k: v for k, v in counters.items()
                   if k.startswith("fallback_tier_transitions")}
    want = f"fallback_tier_transitions{{device={platform},engine={engine}}}"
    check(set(transitions) == {want},
          f"fallback tier transitions {transitions}, expected only {want}")
    check(global_policy().force is None, "the engine tier was forced")
    sup = global_supervisor().stats()
    bad = {c: sup[c] for c in ("demotions", "quarantines",
                               "host_quarantines", "host_completions")
           if sup[c]}
    check(not bad, f"supervisor left the device path: {bad}")
    (perf,) = global_perf().dump().values()
    check(perf.get("ec_device_calls", 0) > 0, "no device EC calls")
    degraded = counters.get("engine_mesh_degraded", 0)
    check(degraded == 0, f"{degraded} engine_mesh_degraded events")
    interpreted = sum(pallas_calls)
    check(interpreted == 0,
          f"{interpreted} pallas_call(s) built with interpret=True")
    row = {"fallback_tier_transitions": transitions,
           "supervisor_dispatches": sup["dispatches"],
           "ec_device_calls": perf["ec_device_calls"],
           "ec_host_calls": perf.get("ec_host_calls", 0),
           "pallas_calls": len(pallas_calls),
           "engine_mesh_degraded": degraded,
           "jax_backend_compiles": counters.get("jax_backend_compiles", 0),
           "jax_persistent_cache_hits":
               counters.get("jax_persistent_cache_hits", 0),
           "jax_persistent_cache_misses":
               counters.get("jax_persistent_cache_misses", 0)}
    emit("no_fallback", **row)
    return row


def multichip_phase(n_devices: int, stripes: int, stripe_size: int,
                    n_osds: int, rep_pgs: int, ec_pgs: int,
                    seed: int) -> dict:
    """RS encode through the data plane over ``n_devices`` and the
    sharded bulk CRUSH sweep of the EC pool (which spans every visible
    device, so ``n_devices`` must be all of them), each byte-identical
    to the one-device result of this process; outputs must span
    ``n_devices`` distinct devices and no plane may degrade.  The
    replicated pool stays out: its programs cost minutes of TPU
    compile per call, four chips at a time."""
    import jax
    from ceph_tpu.parallel import plane

    check(jax.device_count() == n_devices,
          f"{n_devices} devices wanted, jax sees {jax.device_count()}")
    plane.deactivate()
    one = encode_phase(stripes, stripe_size, seed)
    ec = one["ec"]
    k = ec.get_data_chunk_count()
    resident = jax.device_put(one["chunks"][:, :k, :], jax.devices()[0])
    single = np.asarray(ec.encode_chunks_jax(resident))
    dp = plane.activate(n_devices)
    check(dp is not None and dp.n_devices == n_devices,
          f"data plane did not form over {n_devices} devices: {dp}")
    try:
        c0 = _counters()
        t0 = time.perf_counter()
        out = ec.encode_chunks_jax(resident).block_until_ready()
        secs = time.perf_counter() - t0
        spread = len(out.sharding.device_set)
        check(spread == n_devices,
              f"mesh encode output spans {spread} devices")
        check(np.array_equal(np.asarray(out), single),
              "mesh encode differs from the one-device encode")
        tiers = _tiers(c0)
        check("mesh" in tiers, f"encode did not take the mesh tier: "
                               f"{tiers}")
        emit("multichip_encode", devices=spread, tiers=tiers,
             bytes_in=int(resident.nbytes), seconds=secs,
             compiles=_compiles(c0))
    finally:
        plane.deactivate()
    pools = POOLS[1:]
    single_up = crush_phase(n_osds, rep_pgs, ec_pgs, 0, seed, "bulk",
                            pools)
    c1 = _counters()
    sharded_up = crush_phase(n_osds, rep_pgs, ec_pgs, 0, seed, "sharded",
                             pools)
    mesh = _delta(c1, "engine_mesh_dispatches")
    want = f"engine_mesh_dispatches{{devices={n_devices},tier=crush-bulk}}"
    check(mesh.get(want, 0) > 0, f"sharded CRUSH mesh dispatches {mesh}")
    for pool, up in single_up.items():
        check(np.array_equal(sharded_up[pool], up),
              f"{pool}: sharded pg_to_up_bulk differs from one device")
    degraded = _counters().get("engine_mesh_degraded", 0)
    check(degraded == 0, f"{degraded} engine_mesh_degraded events")
    emit("multichip_crush", devices=n_devices, mesh_dispatches=mesh,
         pools={p: int(u.shape[0]) for p, u in single_up.items()})
    return {"devices": n_devices}


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_device("tpu", args.chips)
    from ceph_tpu.telemetry import install_compile_monitor
    from ceph_tpu.utils.compile_cache import initialize_compile_cache
    cache_dir = initialize_compile_cache()
    install_compile_monitor()
    pallas_calls = watch_pallas_calls()
    emit("compile_cache", dir=cache_dir)

    cluster = dict(n_osds=1000, rep_pgs=32768, ec_pgs=8192)
    if args.chips == 4:
        multichip_phase(4, stripes=256, stripe_size=1 << 20,
                        seed=args.seed, **cluster)
    else:
        rs = encode_phase(256, 1 << 20, args.seed)
        decode_phase(rs, 256, shec_size=6 * 131072, clay_size=1 << 20,
                     seed=args.seed)
        del rs
        crush_phase(oracle_pgs=4096, seed=args.seed, **cluster)
        # the EC path once more, after placement ran in this process
        encode_phase(256, 1 << 20, args.seed + 3,
                     phase="encode_after_crush")
        no_fallback_phase("tpu", "pallas", pallas_calls)
        check(len(pallas_calls) > 0, "no Pallas kernel on the path")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
