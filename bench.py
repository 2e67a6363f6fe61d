#!/usr/bin/env python3
"""North-star benchmark: jerasure-equivalent encode, k=8 m=3, 1 MiB stripes.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

- value: batched encode GB/s (input bytes / elapsed) on the TPU,
  measured as --loop chained encodes inside a single dispatch: kernel +
  HBM traffic with per-dispatch latency amortized away ("percall_gbps"
  records the per-call number beside it).  The platform is pinned and
  checked in-process: without a TPU, or when every device run fails,
  the script exits nonzero and prints no result line.
- vs_baseline: ratio against the in-tree C++ AVX2 Reed-Solomon plugin
  (native/plugins/rs.cc via native/tools/ceph_erasure_code_benchmark.cc)
  run on this host — the honest stand-in for the reference's
  jerasure-SIMD CPU path (BASELINE.md; the reference binary itself is
  unbuildable here, mount empty).  Measured live when the native build
  exists, else "not measured" (null).
- decode_gbps / decode_rows: chained device decode GB/s for the same RS
  shape plus BASELINE rows 3-4 (shec single-chunk decode, clay repair)
  — the decode path IS the recovery math (SURVEY §5), so it belongs in
  the official artifact, not just in tools/bench_rows.sh.
- vs_host_groundtruth: secondary ratio against the numpy region ops
  (the framework's own host ground truth — NOT a CPU-optimized
  baseline; renamed from the r01/r02 "vs_numpy" field, which invited
  quoting it as a speedup).

Config matches BASELINE.json north_star: plugin=jerasure,
technique=reed_sol_van, k=8, m=3, 1 MiB stripes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench

REPO = os.path.dirname(os.path.abspath(__file__))

# Series marker for cross-round trend consumers.  Each version added
# fields; consumers that read only `value`/`decode_rows` are unaffected.
#   v2  headline `value` pinned to the carry chain; `slice_gbps` beside it
#   v3  decode/degraded rows carry lat_p50/p99/p999_ms; `telemetry` blob
#   v4  `serving_rows` (GB/s under SLO, request latency, warm compiles)
#   v5  `topology` on every line; `multichip_rows` (mesh-sharded tier)
#   v6  `cluster_rows` (storm -> balance -> rateless recover)
#   v7  `profile_rows` (per-program cost / roofline attribution)
#   v8  `scenario_rows` (mixed day under mClock QoS)
#   v9  decode rows carry `engine` and `xor_schedule`
#   v10 `device_chaos_rows` and the top-level `supervisor` blob
#   v11 `autotune_rows`; every row carries config_source/tune_key_hash
#   v12 serving/scenario rows carry `tail_attribution`
#   v13 audit meta carries `lockcheck`
#   v14 `host_chaos_rows` (host fault-domain loss)
#   v15 serving rows gained a page-pool twin row (since removed)
#   v16 `tenant_week_rows` (multi-tenant compressed week)
#   v17 audit meta carries `detcheck`
#   v18 no host-only error line: without a TPU, or when every device
#       run fails, bench.py exits nonzero and prints nothing; no
#       embedded last-good record; `topology` names device_kind; the
#       native CPU baseline is null ("not measured") when its binary
#       is absent
METRIC_VERSION = 18

NORTH_STAR = ["--plugin", "jerasure",
              "--parameter", "technique=reed_sol_van",
              "--parameter", "k=8", "--parameter", "m=3",
              "--size", str(1 << 20), "--workload", "encode"]

# Device decode rows (BASELINE.md rows 3-4 + the north-star shape).
# batch/loop sizes mirror tools/bench_rows.sh: large enough to amortize
# per-dispatch latency, small enough to keep one bench run bounded on
# the heavier codes.
DECODE_ROWS = [
    ("rs_k8_m3_e2",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3", "--size", str(1 << 20),
      "--workload", "decode", "-e", "2",
      "--device", "jax", "--batch", "64", "--loop", "1024",
      "--layout", "packed", "--chain", "slice"]),
    # shec decode now routes through the unified composite engine: the
    # plan matrix runs the generalized packed Pallas kernel, which is
    # opaque to XLA DCE, so the packed slice chain is valid for it.
    ("shec_k6_m3_c2_e1",
     ["--plugin", "shec", "--parameter", "k=6", "--parameter", "m=3",
      "--parameter", "c=2", "--size", str(6 * 131072),
      "--workload", "decode", "-e", "1",
      "--device", "jax", "--batch", "32", "--loop", "256",
      "--layout", "packed", "--chain", "slice"]),
    # clay's 64x704 single-erasure composite routes to the MXU einsum
    # (pure XLA, NOT DCE-opaque — the bench gate rejects slice for
    # it), so it runs packed + carry: one packed dispatch per step,
    # conservative chain accounting.
    ("clay_k8_m4_d11_e1",
     ["--plugin", "clay", "--parameter", "k=8", "--parameter", "m=4",
      "--parameter", "d=11", "--size", str(1 << 20),
      "--workload", "decode", "-e", "1",
      "--device", "jax", "--batch", "16", "--loop", "64",
      "--layout", "packed", "--chain", "carry"]),
]

# Degraded / recovery-path rows (ISSUE 2): deep-scrub verify + repair
# GB/s for the north-star RS shape at 0 faults (pure scrub verify), 1
# erasure, and the full m-fault budget spent as m-1 erasures + 1
# corruption (the corruption exercises detect→demote→decode, not just
# decode).  Host-side by design — the scrub crc and classification are
# host math.
DEGRADED_COMMON = ["--plugin", "jerasure",
                   "--parameter", "technique=reed_sol_van",
                   "--parameter", "k=8", "--parameter", "m=3",
                   "--size", str(1 << 20), "--workload", "degraded",
                   "--device", "host", "--batch", "4"]
DEGRADED_ROWS = [
    ("rs_k8_m3_scrub_e0", ["-e", "0"]),
    ("rs_k8_m3_degraded_e1", ["-e", "1"]),
    ("rs_k8_m3_degraded_e2_c1", ["-e", "2", "--corruptions", "1"]),
    # batched scrub repair (unified engine): 16 objects of 256 KiB
    # grouped by erasure pattern, ONE fused decode→re-encode dispatch
    # per pattern batch — measured every round so the batching win
    # (and the device-call count staying == pattern count) is
    # tracked.  argparse last-wins lets the row override the common
    # workload/device/size.
    ("rs_k8_m3_repair_batched_e1",
     ["--workload", "repair-batched", "--device", "jax",
      "--size", str(1 << 18), "--batch", "16", "-e", "1"]),
    # recovery under live OSDMap churn (ISSUE 4): the epoch-aware
    # orchestrator drives the same batched repair to durable
    # convergence while a seeded MapChurn advances the map every 2
    # pattern-batch dispatches — epoch fencing, re-plans, regroups and
    # the intent journal all inside the timed loop, so this row tracks
    # the fencing overhead against the still-map repair-batched row.
    ("rs_k8_m3_recovery_churn",
     ["--workload", "recovery-churn", "--device", "jax",
      "--size", str(1 << 18), "--batch", "8", "-e", "1",
      "--churn-every", "2"]),
]


# Serving rows (ISSUE 7): the canonical mixed rs/shec/clay stream
# (serve.loadgen.default_spec) driven closed-loop through the
# admission queue + continuous batcher, REAL clock — tail latency and
# GB/s-under-SLO, the axes the offline rows cannot see.  Byte-verified
# against ground truth inside the workload.
SERVING_ROWS = [
    ("serving_mixed_closed",
     ["--workload", "serving", "--device", "jax",
      "--size", str(1 << 16), "--requests", "256",
      "--concurrency", "64", "--seed", "42"]),
]


# Multichip rows (ISSUE 8): the mesh data plane — encode fanned out
# across every visible device through the engine's sharded tier, ONE
# dispatch per batch, byte-verified in-workload against the
# single-device engine.  On a single-chip host the plane degrades to
# single-device and the row says so (n_devices/mesh_shape), so the
# scaling table is never fiction.
MULTICHIP_ROWS = [
    ("rs_k8_m3_multichip",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3",
      "--size", str(1 << 20), "--workload", "multichip",
      "--device", "jax", "--batch", "64", "--iterations", "8"]),
]


# Cluster rows (ISSUE 9): the 10k-OSD cluster plane scaled to a
# bench-bounded 1000 devices per round — churn storm through the
# incremental path (remap convergence via the bulk evaluator, pinned
# equivalent to rebuild + catch_up in-workload), the device-closed
# balancer loop to max deviation <= 1, and rateless first-k recovery
# under a 10x straggler with the no-straggler control ratio.
CLUSTER_ROWS = [
    ("cluster_1k_storm_balance_recover",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=4", "--parameter", "m=2",
      "--size", str(1 << 16), "--workload", "cluster",
      "--device", "jax", "--osds", "1000", "--cluster-pgs", "1024",
      "--storm-events", "40", "--batch", "8", "--seed", "42"]),
]

# Profile rows (ISSUE 10): the device-plane profiler over the
# north-star shape — serve encode/decode + fused repair through the
# engine's cached programs, per-program cost/roofline attribution
# joined with measured dispatch latency.  The row's GB/s is the mixed
# three-program loop (not a headline — the attribution table is the
# payload).
PROFILE_ROWS = [
    ("rs_k8_m3_profile",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3",
      "--size", str(1 << 18), "--workload", "profile",
      "--device", "jax", "--batch", "16", "--iterations", "4",
      "-e", "1"]),
]


# Scenario rows (ISSUE 11): the composed production day — the mixed
# client stream at SLO + churn storm + straggler recovery under
# mClock QoS arbitration, one real clock (--workload scenario;
# ceph_tpu/scenario/, docs/SCENARIOS.md).  Correctness (byte-verified
# stream, byte-identical heal) gates in-workload; the row's
# gbps_under_slo is the bench_diff `scenario` series, so
# p99-under-contention cannot silently regress.
SCENARIO_ROWS = [
    ("scenario_mixed_day",
     ["--workload", "scenario", "--device", "jax",
      "--size", str(1 << 14), "--requests", "128", "--batch", "4",
      "-e", "1", "--storm-events", "6", "--seed", "42"]),
]

# Tenant-week rows (ISSUE 19): the pinned 3-tenant compressed week —
# diurnal client streams merged on one timeline, the noisy tenant's
# burst storm clamped at the door by its mClock limit tag, four
# staged disasters healing byte-identically — as a deterministic
# EventClock simulation (--workload tenant-week;
# ceph_tpu/scenario/week.py, docs/SCENARIOS.md).  Correctness
# (converged + byte-identical heal + byte-verified stream) and the
# isolation gate (victims' p99/miss-rate vs isolated baselines) gate
# in-workload; the row's victim_gbps_under_slo is the bench_diff
# `tenant_isolation` series, so noisy-neighbor leakage cannot
# silently regress.
TENANT_WEEK_ROWS = [
    ("tenant_week_isolation",
     ["--workload", "tenant-week", "--device", "host",
      "--iterations", "2", "--seed", "17"]),
]

TENANT_WEEK_ROW_FIELDS = (
    "gbps_under_slo", "victim_gbps_under_slo", "deadline_miss_rate",
    "arbiter_enabled", "isolation_ok", "isolation_victims",
    "tenants", "disasters_healed", "fence_deferrals",
    "recovery_rounds", "scrub_ticks", "churn_events",
    "requests_offered", "dispatched", "dispatch_crc", "verified")


# Device-chaos rows (ISSUE 13): batched recovery through the
# supervised fused-repair seam while a seeded DispatchFault script
# fires mid-run — transient (bounded retry), HBM OOM (batch-rung
# downshift), persistent backend loss (live tier demotion, numpy-twin
# completion, health-probe re-promotion).  Byte-identical heal and
# zero data loss gate in-workload; the GB/s is the bench_diff
# `device_chaos` series so recovery-under-fault cannot silently
# regress.
DEVICE_CHAOS_ROWS = [
    ("rs_k8_m3_device_chaos",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3",
      "--size", str(1 << 16), "--workload", "device-chaos",
      "--device", "jax", "--batch", "8", "--iterations", "2",
      "-e", "1", "--seed", "42"]),
]

DEVICE_CHAOS_ROW_FIELDS = ("supervisor", "faults_fired",
                           "demoted_at_end", "erasures", "verified")


# Host-chaos rows (ISSUE 17): batched recovery through the supervised
# fused-repair seam while a seeded HostLoss takes a whole simulated
# host fault domain out mid-run — host-granular reshrink (the
# survivor keeps its devices), journal-reclaim hook, health-probe
# re-promotion to full host width.  Byte-identical heal and zero data
# loss gate in-workload; the GB/s is the bench_diff `host_chaos`
# series.
HOST_CHAOS_ROWS = [
    ("rs_k8_m3_host_chaos",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3",
      "--size", str(1 << 19), "--workload", "host-chaos",
      "--device", "jax", "--batch", "8", "--iterations", "2",
      "--hosts", "2", "-e", "1", "--seed", "42"]),
]

HOST_CHAOS_ROW_FIELDS = ("supervisor", "faults_fired",
                         "reclaim_calls", "demoted_at_end", "hosts",
                         "erasures", "verified")


# Autotune rows (ISSUE 14): the profiler-driven config sweep for the
# north-star shape — timed min-of-N candidate dispatches on the
# device.  utilization_pct is the
# bench_diff `autotune` category series; the row also carries the
# tuner's own before/after rows and the tuned-key list, so the round
# artifact shows WHAT was tuned, not just that something was.
AUTOTUNE_ROWS = [
    ("rs_k8_m3_autotune",
     ["--plugin", "jerasure", "--parameter", "technique=reed_sol_van",
      "--parameter", "k=8", "--parameter", "m=3",
      "--size", str(1 << 18), "--workload", "autotune",
      "--device", "jax", "--batch", "16", "--iterations", "3",
      "--seed", "42"]),
]

AUTOTUNE_ROW_FIELDS = ("mode", "n_tuned", "tuned_keys",
                       "utilization_pct", "improvement_pct",
                       "improved_rows", "rows", "verified")


def _section(label: str, rows, fields=()) -> dict:
    """Run each (name, argv) row: name -> _row_result + ``fields``
    copied from the workload result, or None (the cause goes to
    stderr) when the row fails."""
    out = {}
    for name, argv in rows:
        try:
            res = _run(list(argv))
            row = _row_result(res)
            for f in fields:
                row[f] = res.get(f)
            out[name] = row
        except Exception as e:  # noqa: BLE001 - recorded, never fatal
            out[name] = None
            print(f"{label}/{name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
    return out


def _supervisor_blob() -> dict:
    """The process supervisor's cumulative counters + demotion state
    (metric_version 10): whether the run survived device faults and
    on which tier it finished."""
    try:
        from ceph_tpu.ops.supervisor import global_supervisor
        return global_supervisor().stats()
    except Exception as e:  # noqa: BLE001 — metadata never kills bench
        return {"error": f"{type(e).__name__}: {e}"}


SCENARIO_ROW_FIELDS = (
    "gbps_under_slo", "deadline_miss_rate", "arbiter_enabled",
    "qos_scale_min", "qos_burn_trips", "slo_burn_trips",
    "recovery_rounds", "recovery_ops_completed", "churn_events",
    "straggler_reassignments", "rateless_p99_ratio",
    "stream_compiles", "requests", "verified", "tail_attribution")

CLUSTER_ROW_FIELDS = (
    "osds", "total_pgs", "engine", "storm_events",
    "remap_convergence_epochs", "mean_remap_fraction",
    "balancer_iterations", "balancer_converged",
    "balancer_max_dev_final", "p99_recovery_ms", "p99_baseline_ms",
    "p99_ratio", "straggler_reassignments", "redundancy", "verified")

MULTICHIP_ROW_FIELDS = ("n_devices", "mesh_shape", "stripes_per_device",
                        "platform", "verified")

SERVING_ROW_FIELDS = ("gbps_under_slo", "deadline_miss_rate",
                      "padding_overhead", "requests", "rejected",
                      "stream_compiles", "tail_attribution",
                      "cached_programs")

PROFILE_ROW_FIELDS = ("programs", "profile_rows")


def _row_result(res: dict, digits: int = 4) -> dict:
    """metric_version 3 row shape: GB/s plus the per-stripe-batch
    latency percentiles the workload's histogram recorded; since
    metric_version 11 every row also carries its config provenance
    (config_source tuned|default + the installed table's content
    hash — ceph_tpu/tune/, docs/PERF.md 'Roofline-closing
    autotuner')."""
    row = {"gbps": round(res["gbps"], digits)}
    for f in ("lat_p50_ms", "lat_p99_ms", "lat_p999_ms"):
        row[f] = (round(res[f], 4) if res.get(f) is not None else None)
    row["lat_samples"] = res.get("lat_samples")
    row["config_source"] = res.get("config_source", "default")
    row["tune_key_hash"] = res.get("tune_key_hash")
    return row


def _telemetry_blob() -> dict:
    """Compact unified-metrics summary for the one-line artifact:
    counters/gauges verbatim, histograms collapsed to
    count + p50/p99/p999, spans to root/dropped counts.  The full
    dump (buckets, events, span trees) is tools/perf_dump.py's job —
    the bench line must stay one line."""
    try:
        from ceph_tpu import telemetry
        dump = telemetry.dump_all()
    except Exception as e:  # noqa: BLE001 — metadata never kills bench
        return {"error": f"{type(e).__name__}: {e}"}
    out: dict = {"schema_version": dump.get("schema_version")}
    for section, body in dump.items():
        if section in ("schema_version", "spans"):
            continue
        compact = {}
        for key, v in body.items():
            if key == "__events__":
                compact["events"] = len(v)
            elif isinstance(v, dict) and "buckets" in v:
                compact[key] = {k: v[k] for k in
                                ("count", "p50", "p99", "p999")}
            else:
                compact[key] = v
        out[section] = compact
    spans = dump.get("spans", {})
    out["spans"] = {"roots": len(spans.get("spans", ())),
                    "dropped": spans.get("dropped", 0)}
    return out


def _degraded_rows(iterations: int) -> dict:
    """name -> {gbps, lat_*} (None on failure) for the recovery-path
    rows (metric_version 3 row shape)."""
    return _section("degraded", [
        (name, DEGRADED_COMMON + ["--iterations", str(iterations)]
         + extra) for name, extra in DEGRADED_ROWS])


def _audit_meta() -> dict:
    """Which code shapes this bench's numbers are certified for:
    the tpu-audit entry-point registry size and trace-rule ids
    (docs/LINT.md "Trace tier").  Declarative reads only — no jax
    tracing at bench time; the audit itself gates tier-1."""
    try:
        from ceph_tpu.analysis.entrypoints import registry
        from ceph_tpu.analysis.jaxpr_audit import AUDIT_RULE_IDS
        from ceph_tpu.utils.detcheck import detcheck_enabled
        from ceph_tpu.utils.locks import lockcheck_enabled
        return {
            "audited_entrypoints": len(registry()),
            "audit_rules": sorted(AUDIT_RULE_IDS),
            # whether the instrumented-lock validator was live for
            # this run (CEPH_TPU_LOCKCHECK=1): checked locks add a
            # bookkeeping step per acquire, so a row measured under
            # lockcheck is not comparable to a production row
            "lockcheck": lockcheck_enabled(),
            # same rule for the determinism tripwire
            # (CEPH_TPU_DETCHECK=1): wrapped clock seams add a
            # witness branch per consultation
            "detcheck": detcheck_enabled(),
        }
    except Exception:  # noqa: BLE001 — metadata must never kill a bench
        return {"audited_entrypoints": None, "audit_rules": [],
                "lockcheck": False, "detcheck": False}


def _run(argv: list[str]) -> dict:
    bench = ErasureCodeBench()
    bench.setup(argv)
    return bench.run()


def _cpp_baseline() -> tuple[float | None, str]:
    """(GB/s, provenance) of the native C++ RS benchmark; (None,
    "not measured") when its binary is absent or fails."""
    exe = os.path.join(REPO, "native", "build",
                       "ceph_erasure_code_benchmark")
    if os.path.exists(exe):
        try:
            out = subprocess.run(
                [exe, "-p", "rs", "-w", "encode", "-i", "100",
                 "-s", str(1 << 20), "-P", "k=8", "-P", "m=3",
                 "-d", os.path.dirname(exe)],
                capture_output=True, text=True, timeout=300, check=True)
            elapsed, kib = out.stdout.split()
            gbps = float(kib) * 1024 / float(elapsed) / 1e9
            return gbps, "cpp-rs-avx2 (measured live)"
        except Exception:  # noqa: BLE001 - reported as not measured
            pass
    return None, "not measured"


def _device() -> dict:
    """The device the run measures, as jax reports it.  With
    JAX_PLATFORMS unset the platform is pinned to tpu first, so a
    TPU that fails to initialize raises instead of falling back to
    the CPU."""
    import jax
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _topology(dev: dict) -> dict:
    """The per-line topology blob: the measured device plus the
    active data-plane mesh shape, if any."""
    topo = dict(dev, mesh_shape=None)
    try:
        from ceph_tpu.parallel.plane import plane_topology
        topo["mesh_shape"] = plane_topology()
    except Exception:  # noqa: BLE001 — metadata never kills bench
        pass
    return topo


def main() -> int:
    try:
        dev = _device()
    except RuntimeError as e:
        print(f"bench: no TPU backend: {e}", file=sys.stderr)
        return 1
    if dev["platform"] != "tpu":
        print(f"bench: no TPU device (jax reports {dev}); nothing "
              f"measured", file=sys.stderr)
        return 1
    from ceph_tpu.telemetry import install_compile_monitor
    from ceph_tpu.utils.compile_cache import initialize_compile_cache
    # jax.monitoring compile events → the telemetry registry, so the
    # line's telemetry blob records how many programs this run built
    install_compile_monitor()
    initialize_compile_cache()
    # CPU baseline: numpy reference region ops, small batch.
    host = _run(NORTH_STAR + ["--device", "host", "--batch", "4",
                              "--iterations", "3"])
    cpp_gbps, cpp_src = _cpp_baseline()
    # device throughput: chained encodes inside one dispatch; 1024
    # loops (= 64 GiB through the kernel) amortize per-dispatch
    # latency.  Two layouts: bytes (uint8 contract at the chain
    # boundary) and packed (the resident uint32 SWAR layout, SURVEY
    # §7 — same bytes, zero repacking inside the chain).
    candidates = []
    errors = []
    # chain=slice carries one element between steps, so measured HBM
    # traffic is exactly the encode's own read+write (1.375x input at
    # k=8 m=3) — the roofline-honest throughput; chain=carry XOR-folds
    # full parities (2.5x input traffic, stream-ceiling bound) and is
    # kept for continuity with the r02-r04 numbers (tools/roofline.py
    # separates the terms; docs/PERF.md has the table).
    for layout, chain in (("packed", "slice"), ("packed", "carry"),
                          ("bytes", "carry")):
        try:
            candidates.append(_run(NORTH_STAR + [
                "--device", "jax", "--batch", "64",
                "--loop", "1024", "--layout", layout,
                "--chain", chain]))
        # SystemExit included: the slice-chain honesty gate raises it
        except (Exception, SystemExit) as e:  # noqa: BLE001
            errors.append(f"encode/{layout}/{chain}: "
                          f"{type(e).__name__}: {e}")
    try:
        percall = _run(NORTH_STAR + ["--device", "jax", "--batch", "64",
                                     "--iterations", "100", "--resident"])
        candidates.append(percall)
    except Exception as e:  # noqa: BLE001
        errors.append(f"encode/percall: {type(e).__name__}: {e}")
        percall = None
    if not candidates:
        print("bench: every device encode run failed: "
              + "; ".join(errors), file=sys.stderr)
        return 1
    # decode rows (BASELINE rows 3-4 + RS shape) — recovery-path GB/s
    # in the official artifact, not only in bench_rows.sh
    decode_rows = {}
    for name, argv in DECODE_ROWS:
        try:
            dres = _run(argv)
            row = _row_result(dres, digits=3)
            # metric_version 9: which engine tier ran the composite
            # decode matrix, and the XOR schedule's stats when the
            # probe scheduled it — the row records why it moved
            row["engine"] = dres.get("engine")
            row["xor_schedule"] = dres.get("xor_schedule")
            decode_rows[name] = row
        except (Exception, SystemExit) as e:  # noqa: BLE001
            errors.append(f"decode/{name}: {type(e).__name__}: {e}")
            decode_rows[name] = None
    # Headline hygiene (metric_version 2): the headline `value` comes
    # from the CARRY-chain candidates only (falling back to per-call if
    # every chained run failed); the slice-chain number is reported
    # separately as `slice_gbps`.
    carry = [c for c in candidates if c.get("chain") != "slice"]
    best = max(carry or candidates, key=lambda r: r["gbps"])
    slice_gbps = max(
        (round(c["gbps"], 3) for c in candidates
         if c.get("chain") == "slice" and c.get("loop")), default=None)
    out = {}
    if errors:
        # some device runs failed while others succeeded: flag it so a
        # partial line is never mistaken for a clean measurement
        out["partial_error"] = "; ".join(errors)
    out |= {
        "metric": "encode_gbps_jerasure_rs_k8_m3_1MiB_stripes",
        "metric_version": METRIC_VERSION,
        "value": round(best["gbps"], 3),
        "unit": "GB/s",
        "vs_baseline": (round(best["gbps"] / cpp_gbps, 3)
                        if cpp_gbps else None),
        "baseline": cpp_src,
        "baseline_gbps": round(cpp_gbps, 3) if cpp_gbps else None,
        "layout": best.get("layout", "bytes"),
        "chain": best.get("chain", "carry"),
        "carry_chain_gbps": max(
            (round(c["gbps"], 3) for c in candidates
             if c.get("chain") == "carry" and c.get("loop")),
            default=None),
        "slice_gbps": slice_gbps,
        "percall_gbps": round(percall["gbps"], 3) if percall else None,
        "topology": _topology(dev),
        "decode_gbps": (decode_rows.get("rs_k8_m3_e2") or {}).get("gbps"),
        "decode_rows": decode_rows,
        "degraded_rows": _degraded_rows(iterations=3),
        "serving_rows": _section("serving", SERVING_ROWS,
                                 SERVING_ROW_FIELDS),
        "multichip_rows": _section("multichip", MULTICHIP_ROWS,
                                   MULTICHIP_ROW_FIELDS),
        "cluster_rows": _section("cluster", CLUSTER_ROWS,
                                 CLUSTER_ROW_FIELDS),
        "profile_rows": _section("profile", PROFILE_ROWS,
                                 PROFILE_ROW_FIELDS),
        "scenario_rows": _section("scenario", SCENARIO_ROWS,
                                  SCENARIO_ROW_FIELDS),
        "tenant_week_rows": _section("tenant-week", TENANT_WEEK_ROWS,
                                     TENANT_WEEK_ROW_FIELDS),
        "device_chaos_rows": _section("device-chaos", DEVICE_CHAOS_ROWS,
                                      DEVICE_CHAOS_ROW_FIELDS),
        "host_chaos_rows": _section("host-chaos", HOST_CHAOS_ROWS,
                                    HOST_CHAOS_ROW_FIELDS),
        "autotune_rows": _section("autotune", AUTOTUNE_ROWS,
                                  AUTOTUNE_ROW_FIELDS),
        "lat_p50_ms": best.get("lat_p50_ms"),
        "lat_p99_ms": best.get("lat_p99_ms"),
        "lat_p999_ms": best.get("lat_p999_ms"),
        "vs_host_groundtruth": round(best["gbps"] / host["gbps"], 3)
        if host["gbps"] > 0 else None,
        "supervisor": _supervisor_blob(),
        "telemetry": _telemetry_blob(),
        **_audit_meta(),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
