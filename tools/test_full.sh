#!/bin/sh
# The FULL test suite (round gate / judge run): includes @slow tests.
# The default `pytest -q` selection skips them to keep the edit-test
# loop under ~5 minutes (VERDICT r03 Next#9).
cd "$(dirname "$0")/.."
# Static gate first: tpu-lint must be clean before anything compiles.
# (The same gate runs inside tier-1 as tests/test_tpu_lint.py; running
# it here too makes a lint regression fail in seconds, not minutes.)
# bench.py rides along so the round-artifact driver is linted too —
# everything under ceph_tpu/ and tools/ (including any new files) is
# already covered by the directory walks.  --check-suppressions also
# fails the run on stale `# tpu-lint: disable=` pragmas.
python tools/tpu_lint.py --check-suppressions ceph_tpu/ tools/ bench.py \
    || exit 1
# Concurrency gate (conc tier, docs/LINT.md): lock discovery, guard
# inference, the conc-* rules and the lockmodel rank registry
# cross-check — pure AST, jax-free, seconds.  --check-suppressions
# also fails on stale `conc-*` pragmas (the AST gate above skips
# them: conc pragmas are this tier's to judge).  The runtime half
# (CEPH_TPU_LOCKCHECK=1) runs inside tier-1 as tests/test_lockcheck.py.
python tools/tpu_lint.py --conc --check-suppressions ceph_tpu/ tools/ \
    bench.py || exit 1
# Determinism gate (det tier, docs/LINT.md): replay-domain code must
# consult nothing a seeded, clock-injected rerun cannot reproduce —
# wall clocks, unseeded RNGs, set iteration order, call-time environ
# reads — with the sanctioned seams declared in analysis/replaymodel.py
# and cross-checked both ways.  Pure AST, jax-free, seconds.  The
# runtime half (CEPH_TPU_DETCHECK=1) runs inside tier-1 as
# tests/test_detcheck.py; tools/replay_bisect.py is the divergence
# witness.
python tools/tpu_lint.py --det --check-suppressions ceph_tpu/ tools/ \
    bench.py || exit 1
# Determinism smoke (ISSUE 20): the seeded production day must print a
# byte-identical report from two separate interpreters with DIFFERENT
# hash seeds — any set-order leak into the report shows up here as a
# diff before the full suite runs.
PYTHONHASHSEED=1 python tools/scenario_demo.py --json \
    > /tmp/ceph_tpu_det_a.json || exit 1
PYTHONHASHSEED=77 python tools/scenario_demo.py --json \
    > /tmp/ceph_tpu_det_b.json || exit 1
cmp -s /tmp/ceph_tpu_det_a.json /tmp/ceph_tpu_det_b.json \
    || { echo "determinism smoke: report differs across PYTHONHASHSEED"; exit 1; }
# Trace gate second (ISSUE 5): tpu-audit traces every registered
# jit-facing entry point (analysis/entrypoints.py) to a jaxpr, runs
# the audit-* rules + the recompile sentinel, and fails if a public
# plugin device surface is missing from the registry.  Same gate runs
# in tier-1 as tests/test_jaxpr_audit.py.
python tools/tpu_lint.py --trace --check-suppressions || exit 1
# Chaos/scrub end-to-end smoke (docs/ROBUSTNESS.md): a recoverable
# fault mix must heal (rc 0) and a past-budget mix must fail with the
# structured unrecoverable report (rc 2) — in seconds, before the full
# suite runs the seeded fuzz (tests/test_scrub_fuzz.py).
python tools/scrub_demo.py --erasures 1 --corruptions 1 --transient 2 \
    >/dev/null || exit 1
python tools/scrub_demo.py --erasures 3 --corruptions 1 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "scrub_demo: expected unrecoverable rc 2"; exit 1; }
# Recovery-orchestrator end-to-end smoke (ISSUE 4): a seeded
# churn+crash+torn-write scenario must converge byte-identical through
# the epoch fence and the intent journal (rc 0), and a past-budget mix
# must exit with the structured unrecoverable report (rc 2) — the full
# torture sweep runs inside tier-1 as tests/test_recovery_churn.py.
python tools/recovery_demo.py --erasures 1 --corruptions 1 --churn 3 \
    --crash-site writeback.after_write --torn >/dev/null || exit 1
python tools/recovery_demo.py --erasures 3 --churn 0 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "recovery_demo: expected unrecoverable rc 2"; exit 1; }
# Telemetry gate (ISSUE 6 / docs/OBSERVABILITY.md): a seeded repair +
# recovery-churn scenario must produce a schema-valid unified dump
# (spans + metrics; byte-identical under --fake-clock, which the
# tier-1 tests pin), and instrumentation overhead on the host-path
# bench row must stay under 3%.
python tools/perf_dump.py --scenario both --fake-clock --validate \
    >/dev/null || { echo "perf_dump: telemetry schema gate failed"; exit 1; }
python tools/perf_dump.py --check-overhead 3 \
    || { echo "perf_dump: instrumentation overhead above 3%"; exit 1; }
# Causal-tracing gates (ISSUE 15 / docs/OBSERVABILITY.md "Causal
# tracing & tail attribution"): (a) the seeded FakeClock production
# day under the trace collector must emit a schema-valid unified dump
# whose `traces` section validates (trace_schema_version 1);
# (b) trace_view's gate mode pins exact segment sums AND byte-
# identical replay across two runs of one seed; (c) the <=3% overhead
# bound must hold with the collector ACTIVE (tracing-enabled runs).
python tools/perf_dump.py --scenario traced-day --fake-clock --traces \
    --validate >/dev/null \
    || { echo "perf_dump: causal-tracing schema gate failed"; exit 1; }
python tools/trace_view.py --run-scenario --check >/dev/null \
    || { echo "trace_view: tracing determinism/decomposition gate failed"; exit 1; }
python tools/perf_dump.py --check-overhead 3 --with-traces \
    || { echo "perf_dump: tracing-enabled overhead above 3%"; exit 1; }
# Device-plane profiler gates (ISSUE 10 / docs/OBSERVABILITY.md
# "Device-plane profiler"): (a) EVERY jit-tier audited entry point
# must produce a cost/roofline attribution row (rc 1 inside perf_dump
# when one goes row-less), under a schema-valid (v2) dump; (b) a
# seeded past-budget repair must freeze a byte-identical, schema-valid
# flight-recorder post-mortem; (c) tools/bench_diff.py must pass rc0
# on the checked-in BENCH_r*.json trajectory — the perf-regression
# sentinel every subsequent perf PR is judged with.
python tools/perf_dump.py --scenario none --profile --validate \
    >/dev/null || { echo "perf_dump: profiler coverage gate failed"; exit 1; }
python tools/perf_dump.py --scenario unrecoverable --fake-clock \
    --flight-recorder --validate >/dev/null \
    || { echo "perf_dump: flight-recorder gate failed"; exit 1; }
python tools/bench_diff.py \
    || { echo "bench_diff: perf regression against the BENCH_* trajectory"; exit 1; }
# Autotune gate (ISSUE 14 / docs/PERF.md "Roofline-closing
# autotuner"): the host-only analytic sweep must run with zero jax
# compiles, emit a schema-valid best-config table that round-trips,
# and be byte-identical across two runs from one seed — the mode the
# tune.sweep audit entry relies on.
python tools/autotune.py --analytic --out /tmp/ceph_tpu_tune_smoke.json \
    --validate >/dev/null \
    || { echo "autotune: analytic smoke gate failed"; exit 1; }
# Serving gate (ISSUE 7 / docs/SERVING.md): the seeded mixed
# rs/shec/clay stream with the chaos-degraded repair slice must serve
# byte-identical under a schema-valid telemetry dump (rc 0), and an
# erasure budget past every code's decode capability must exit with
# the structured unrecoverable report (rc 2) — the 500-request
# zero-recompile stream runs inside tier-1 as tests/test_serve.py.
python tools/serve_demo.py --requests 48 --validate >/dev/null \
    || { echo "serve_demo: serving gate failed"; exit 1; }
python tools/serve_demo.py --erasures 4 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "serve_demo: expected unrecoverable rc 2"; exit 1; }
# Cluster-plane gates (ISSUE 9 / docs/CLUSTER.md): the seeded
# storm -> balance -> rateless-recover scenario must hold every gate
# (storm incremental == rebuilt == catch_up, balancer converged to
# max deviation <= 1 with device-loop proposals byte-identical to the
# host loop, zero data loss under the injected straggler) at rc 0,
# and a past-budget erasure mix must exit with the structured
# unrecoverable report (rc 2).
python tools/cluster_demo.py --osds 240 --pgs 256 --events 12 \
    >/dev/null || { echo "cluster_demo: cluster gate failed"; exit 1; }
python tools/cluster_demo.py --osds 120 --pgs 256 --events 8 \
    --verify-host-loop >/dev/null \
    || { echo "cluster_demo: host-loop identity gate failed"; exit 1; }
python tools/cluster_demo.py --osds 120 --pgs 128 --events 6 \
    --erasures 3 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "cluster_demo: expected unrecoverable rc 2"; exit 1; }
# The 10k-OSD acceptance scenario on the simulated 8-device mesh
# (ISSUE 9): the same end-to-end run at full scale, the bulk
# evaluator riding an 8-way forced-CPU data plane.
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    CEPH_TPU_MESH=auto \
    python tools/cluster_demo.py --osds 10000 --pgs 2048 --events 30 \
    --measure-every 5 >/dev/null \
    || { echo "cluster_demo: 10k simulated-mesh gate failed"; exit 1; }
# Scenario gates (ISSUE 11 / docs/SCENARIOS.md): the composed
# production day — client traffic at SLO + churn storm + straggler
# recovery under mClock QoS arbitration — must hold every gate at
# rc 0 (byte-identical replay from the seed, byte-identical client
# stream under contention, byte-identical heal, arbiter-on p99 AND
# miss rate strictly better than the arbiter-off control), and a
# past-budget damage mix must exit with the structured unrecoverable
# report (rc 2).
python tools/scenario_demo.py >/dev/null \
    || { echo "scenario_demo: scenario gate failed"; exit 1; }
python tools/scenario_demo.py --erasures 4 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "scenario_demo: expected unrecoverable rc 2"; exit 1; }
# Tenant-week gates (ISSUE 19 / docs/SCENARIOS.md "Multi-tenant
# weeks"): the seeded 3-tenant compressed week — diurnal streams
# under per-tenant mClock, scrub/churn cadences, and the staged
# disaster schedule (rack loss at peak, backend loss, host loss,
# noisy-neighbor burst) on the discrete-event clock — must hold
# every gate at rc 0: byte-identical replay, discrete-event ==
# stepped-clock report identity, every disaster healed with zero
# data loss, the isolation gate green arbiter-on AND red on the
# arbiter-off control arm.
python tools/tenant_week_demo.py >/dev/null \
    || { echo "tenant_week_demo: multi-tenant week gate failed"; exit 1; }
# Supervised-dispatch-plane gates (ISSUE 13 / docs/ROBUSTNESS.md
# "Supervised dispatch plane"): a seeded production day that loses
# its device backend mid-stream (persistent DispatchFault at the warm
# fused-repair seam) must complete with a byte-identical heal vs the
# unfailed control, a visible live demotion + flight-recorder dump,
# and a logged re-promotion once the fault clears; in self-verify
# mode an injected output-buffer bit flip must be CAUGHT and never
# written back (rc 0) — and a past-budget damage mix must still exit
# with the structured unrecoverable report (rc 2).
python tools/device_chaos_demo.py --corrupt >/dev/null \
    || { echo "device_chaos_demo: supervised dispatch gate failed"; exit 1; }
python tools/device_chaos_demo.py --erasures 4 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "device_chaos_demo: expected unrecoverable rc 2"; exit 1; }
# Host-fault-domain gates (ISSUE 17 / docs/ROBUSTNESS.md "Host fault
# domains"): a seeded production day on a simulated 2-host plane that
# loses a WHOLE host domain mid-stream must complete with a
# byte-identical heal vs the unfailed control, one host-granular
# reshrink (2x4 -> 1x4, host_quarantined flight dump), the lost
# host's in-flight intents re-dispatched, and a re-promotion back to
# full host width once the adversary releases (rc 0); a past-budget
# damage mix must still exit with the structured unrecoverable report
# (rc 2); and the REAL-process version must hold: two worker
# processes, one SIGKILLed mid-batch, the survivor detecting the loss
# by heartbeat ProbeTimeout and finishing byte-identical on the
# shrunken plane (no re-promotion while the peer stays dead).
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python tools/host_chaos_demo.py >/dev/null \
    || { echo "host_chaos_demo: host fault-domain gate failed"; exit 1; }
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python tools/host_chaos_demo.py --erasures 4 >/dev/null 2>&1
[ $? -eq 2 ] || { echo "host_chaos_demo: expected unrecoverable rc 2"; exit 1; }
python tools/host_chaos_demo.py --kill-one >/dev/null \
    || { echo "host_chaos_demo: multi-process kill-one gate failed"; exit 1; }
# Simulated-mesh gate (ISSUE 8 / docs/PERF.md "Multi-chip data
# plane"): the sharded engine tier must hold on an 8-way virtual CPU
# mesh — trace audit of the sharded entry points (shard_map program
# shapes are only real at device_count > 1; the bare --trace above
# runs them in single-device degrade mode) plus the sharded tier-1
# slice, both in a subprocess with the device count forced.
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python tools/tpu_lint.py --trace \
    --entry engine.fused_repair_sharded \
    --entry engine.fused_repair_host_sharded \
    --entry serve.dispatch_sharded \
    --entry ops.apply_matrix_best_sharded \
    --entry crush.bulk_rule_sharded \
    || { echo "simulated-mesh gate: sharded entry audit failed"; exit 1; }
JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_multichip.py tests/test_parallel.py -q \
    || { echo "simulated-mesh gate: sharded tier-1 slice failed"; exit 1; }
CEPH_TPU_FULL=1 exec python -m pytest tests/ -q "$@"
