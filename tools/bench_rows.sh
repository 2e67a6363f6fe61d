#!/bin/sh
# Measure the BASELINE.md table rows on the live device (BASELINE.md §
# rows 3-5 + the north star in both layouts). Run from the repo root on
# a machine with the TPU reachable; each command prints one JSON line /
# statistics block. Results go into BASELINE.md ("Measured on chip"
# notes) and the round's BENCH notes.
#
# Every row's JSON line is ALSO appended, with timestamp + git sha, to
# chiprun_out/bench_rows.jsonl (git-ignored; the chip tool brings the
# directory back).
#
# Since metric_version 3 each row additionally carries
# lat_p50_ms/lat_p99_ms/lat_p999_ms/lat_samples (per-stripe-batch
# latency percentiles, docs/OBSERVABILITY.md); consumers that only
# read `gbps` are unaffected — rows are appended verbatim.
#
# Since metric_version 12 (ISSUE 15) the serving and scenario rows
# carry `tail_attribution` — the per-segment share of p99 time
# (queue_wait / batch_wait / arbiter_hold / retry_backoff /
# device_dispatch / demux) plus the dominant segment, computed from
# the causal tracing plane (telemetry/tracing.py + analyzer.py,
# docs/OBSERVABILITY.md "Causal tracing & tail attribution"), so a
# tail-latency number that moves names which seam moved it.
#
# Since metric_version 9 (ISSUE 12) the decode rows also carry
# `engine` (which tier select_matrix_engine routed the pattern's
# composite matrix to: xor|mxu|pallas|xla) and `xor_schedule` (the
# XOR scheduler's stats — length, xor_ops vs dense_gf_ops,
# reduction_ratio, transform — null when the probe declines), so a
# decode number that moves is self-explaining.  The shec row now
# rides the XOR-scheduled Pallas kernel (docs/PERF.md "XOR-scheduled
# composite kernels"); tools/bench_diff.py tracks the shec/clay rows
# under the dedicated `composite_decode` category.
#
set -e

mkdir -p chiprun_out
LOG=chiprun_out/bench_rows.jsonl
SHA=$(git rev-parse --short=12 HEAD 2>/dev/null || echo unknown)

run_row() {
    row="$1"; shift
    echo "== $row =="
    ts=$(date -u +%Y-%m-%dT%H:%M:%S+00:00)
    if out=$("$@"); then
        echo "$out"
        printf '{"row": "%s", "timestamp": "%s", "git_sha": "%s", "result": %s}\n' \
            "$row" "$ts" "$SHA" "$out" >> "$LOG"
    else
        # a failed row (device error mid-run, OOM) must not silently
        # truncate the sweep: record it and keep measuring
        echo "ROW FAILED: $row" >&2
        printf '{"row": "%s", "timestamp": "%s", "git_sha": "%s", "result": null}\n' \
            "$row" "$ts" "$SHA" >> "$LOG"
    fi
}

run_row "north star encode, bytes layout (BASELINE row *)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<20)) --batch 64 --loop 1024 --json

run_row "north star encode, packed resident layout" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<20)) --batch 64 --loop 1024 --layout packed --json

run_row "north star encode, packed, slice chain (roofline-honest)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<20)) --batch 64 --loop 1024 --layout packed \
    --chain slice --json

run_row "row 3: shec k=6 m=3 c=2 single-chunk decode (XOR-scheduled packed kernel, slice chain)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p shec -P k=6 -P m=3 -P c=2 -s $((6*131072)) \
    --workload decode -e 1 --batch 32 --loop 256 \
    --layout packed --chain slice --json

run_row "row 3b: shec decode, pre-engine shape (bytes/carry, trend continuity)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p shec -P k=6 -P m=3 -P c=2 -s $((6*131072)) \
    --workload decode -e 1 --batch 32 --loop 256 --json

run_row "row 4: clay k=8 m=4 d=11 decode (1 erasure; packed, carry — MXU composite is not DCE-opaque)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p clay -P k=8 -P m=4 -P d=11 -s $((1<<20)) \
    --workload decode -e 1 --batch 16 --loop 64 \
    --layout packed --chain carry --json

run_row "row 4a: clay decode, pre-engine shape (bytes/carry, trend continuity)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p clay -P k=8 -P m=4 -P d=11 -s $((1<<20)) \
    --workload decode -e 1 --batch 16 --loop 64 --json

run_row "row 6: batched scrub repair (one fused dispatch per erasure-pattern batch)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<18)) --workload repair-batched -e 1 --batch 16 \
    --iterations 3 --json

run_row "row 4b: jerasure RS decode, packed layout" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<20)) --workload decode -e 2 --batch 64 --loop 1024 \
    --layout packed --json

run_row "row 7: serving — mixed rs/shec/clay request stream, closed loop (GB/s-under-SLO + latency percentiles; metric_version 4)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    --workload serving -s $((1<<16)) --requests 256 \
    --concurrency 64 --seed 42 --json

run_row "row 8: multichip — mesh-sharded encode over every visible device (ISSUE 8; byte-verified vs single-device, per-device partition in stripes_per_device)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<20)) --workload multichip --batch 64 --iterations 8 --json

run_row "row 9: cluster plane — seeded storm -> balance -> rateless recover over a 1k-OSD synthetic cluster (ISSUE 9; remap convergence, balancer iterations, p99 recovery vs no-straggler control)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=4 -P m=2 \
    -s $((1<<16)) --workload cluster --osds 1000 --cluster-pgs 1024 \
    --storm-events 40 --batch 8 --json

run_row "row 10: device-plane profiler — per-program cost/roofline attribution for the north-star engine programs (ISSUE 10; XLA bytes/FLOPs x measured p50 -> utilization %, metric_version 7)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<18)) --workload profile --batch 16 --iterations 4 \
    -e 1 --json

run_row "row 11: production-day scenario — mixed client stream at SLO + churn storm + straggler recovery under mClock QoS arbitration (ISSUE 11; GB/s-under-SLO and p99 under contention, metric_version 8)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -s $((1<<14)) --workload scenario --requests 128 --batch 4 \
    -e 1 --storm-events 6 --json

run_row "row 12: device-chaos — batched recovery through the supervised fused-repair seam while a seeded transient/OOM/backend-loss script fires mid-run (ISSUE 13; retries, rung downshifts, live demotion + re-promotion in the supervisor counters, metric_version 10)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<16)) --workload device-chaos --batch 8 --iterations 2 \
    -e 1 --json

run_row "row 12b: host-chaos — batched recovery while a seeded HostLoss takes a whole simulated host fault domain out mid-run (ISSUE 17; host-granular reshrink, journal-reclaim hook, re-promotion to full host width in the supervisor counters, metric_version 14)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<19)) --workload host-chaos --batch 8 --iterations 2 \
    --hosts 2 -e 1 --json

run_row "row 13: autotune — profiler-driven config sweep over the bounded declarative space (ISSUE 14; timed min-of-N candidate dispatches, byte-identity asserted per tier, before/after utilization rows + the persisted best-config table, metric_version 11)" \
    python -m ceph_tpu.bench.erasure_code_benchmark \
    -p jerasure -P technique=reed_sol_van -P k=8 -P m=3 \
    -s $((1<<18)) --workload autotune --batch 16 --iterations 3 \
    --seed 42 --json

run_row "row 5: 1M-PG bulk CRUSH sweep on device" \
    python tools/bulk_crush_row.py

run_row "row 5b: 1M-PG bulk CRUSH sweep, canonical EC rule (SET steps)" \
    python tools/bulk_crush_row.py --ec
