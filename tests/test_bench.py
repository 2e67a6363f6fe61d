"""Benchmark harness tests (CLI parity + numbers sane)."""

import json

import numpy as np
import pytest

from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench, main


def run_bench(argv):
    b = ErasureCodeBench()
    b.setup(argv)
    return b.run()


def test_encode_host_smoke():
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--iterations", "2",
                     "--device", "host"])
    assert res["workload"] == "encode"
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"k": "4", "m": "2"})
    assert res["total_bytes"] == 2 * 4 * ec.get_chunk_size(4096)
    assert res["gbps"] > 0


def test_encode_host_matches_reference_cli_output(capsys):
    rc = main(["--plugin", "jerasure",
               "--parameter", "k=2", "--parameter", "m=1",
               "--size", "4096", "--iterations", "1",
               "--device", "host"])
    assert rc == 0
    out = capsys.readouterr().out.strip()
    # reference format: "<seconds>\t<KiB>"
    secs, kib = out.split("\t")
    float(secs)
    assert int(kib) >= 4

def test_encode_json_output(capsys):
    rc = main(["--plugin", "isa",
               "--parameter", "k=4", "--parameter", "m=2",
               "--size", "8192", "--iterations", "1", "--json",
               "--device", "jax"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out)
    assert res["plugin"] == "isa"
    assert res["gbps"] > 0


@pytest.mark.parametrize("gen", ["random", "exhaustive"])
def test_decode_workloads(gen):
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--iterations", "3",
                     "--workload", "decode", "--erasures", "2",
                     "--erasures-generation", gen, "--device", "host"])
    assert res["workload"] == "decode"
    assert res["total_bytes"] > 0


def test_decode_erased_explicit():
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--iterations", "2",
                     "--workload", "decode", "--erased", "0", "--erased", "5",
                     "--device", "host"])
    assert res["total_bytes"] > 0


def test_batch_extension_scales_bytes():
    r1 = run_bench(["--parameter", "k=4", "--parameter", "m=2",
                    "--size", "4096", "--iterations", "1",
                    "--batch", "1", "--device", "host"])
    r8 = run_bench(["--parameter", "k=4", "--parameter", "m=2",
                    "--size", "4096", "--iterations", "1",
                    "--batch", "8", "--device", "host"])
    assert r8["total_bytes"] == 8 * r1["total_bytes"]


def test_loop_mode_chained_encodes():
    """--loop N runs N chained encodes inside one dispatch (device
    throughput with per-dispatch latency amortized); bytes scale with N
    and the XOR-fold of all slab parities is returned."""
    res = run_bench(["--parameter", "k=4", "--parameter", "m=2",
                     "--size", "8192", "--batch", "2",
                     "--device", "jax", "--loop", "5"])
    assert res["total_bytes"] == 5 * 2 * 8192  # ceil to slab count
    assert res["gbps"] > 0


def test_loop_mode_result_is_xor_of_slab_parities():
    """The chained loop must really encode N distinct slabs: its carry
    equals the XOR of per-slab parities computed independently."""
    import numpy as np
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"})
    rng = np.random.default_rng(42)
    chunk = ec.get_chunk_size(8192)
    data = rng.integers(0, 256, (2, 4, chunk), dtype=np.uint8)
    expect = np.zeros((2, 2, chunk), dtype=np.uint8)
    for i in range(5):
        expect ^= np.asarray(ec.encode_chunks_jax(data ^ np.uint8(i)))
    # re-run the harness loop path on the same seed/profile
    import jax
    import jax.numpy as jnp
    slabs = jnp.asarray(
        np.stack([data ^ np.uint8(i) for i in range(5)]))

    def step(carry, slab):
        return carry ^ ec.encode_chunks_jax(slab), None
    out, _ = jax.lax.scan(step, jnp.zeros((2, 2, chunk), jnp.uint8), slabs)
    assert np.array_equal(np.asarray(out), expect)


@pytest.mark.parametrize("plugin,profile", [
    ("jerasure", ["--parameter", "k=4", "--parameter", "m=2"]),
    ("shec", ["--parameter", "k=4", "--parameter", "m=3",
              "--parameter", "c=2"]),
    ("clay", ["--parameter", "k=4", "--parameter", "m=2",
              "--parameter", "d=5"]),
])
def test_decode_loop_mode(plugin, profile):
    """--loop decode: chained device decodes of one erasure pattern
    (the BASELINE decode-row measurement path) for the plugin families
    with distinct repair math."""
    res = run_bench(["--plugin", plugin, *profile, "--size", "8192",
                     "--batch", "2", "--device", "jax",
                     "--workload", "decode", "--erasures", "1",
                     "--loop", "4"])
    assert res["workload"] == "decode"
    assert res["total_bytes"] > 0 and res["gbps"] > 0


def test_degraded_workload_scrub_and_repair():
    """--workload degraded: the recovery-path row (deep_scrub verify +
    classify + repair) with erasures AND a corruption."""
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "2",
                     "--iterations", "2", "--workload", "degraded",
                     "--erasures", "1", "--corruptions", "1",
                     "--device", "host"])
    assert res["workload"] == "degraded"
    assert res["erasures"] == 1 and res["corruptions"] == 1
    assert res["gbps"] > 0
    # total bytes = logical object bytes per iteration
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"k": "4", "m": "2"})
    assert res["total_bytes"] == 2 * 2 * 4 * ec.get_chunk_size(4096)


def test_degraded_workload_pure_scrub():
    """-e 0 with no corruptions times the verify-only deep scrub."""
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "2",
                     "--iterations", "1", "--workload", "degraded",
                     "--erasures", "0", "--device", "host"])
    assert res["workload"] == "degraded" and res["gbps"] > 0


def test_degraded_workload_rejects_over_budget_args():
    with pytest.raises(ValueError, match="clean shards"):
        run_bench(["--plugin", "jerasure",
                   "--parameter", "k=2", "--parameter", "m=1",
                   "--size", "4096", "--workload", "degraded",
                   "--erasures", "2", "--corruptions", "1",
                   "--device", "host"])


def test_bench_degraded_rows_config():
    """bench.py's recovery rows stay within the failure budget and
    cover 0 / 1 / m-combined fault levels plus the batched repair
    row (ISSUE 3) and the churn-fenced recovery row (ISSUE 4)."""
    import bench
    names = [n for n, _ in bench.DEGRADED_ROWS]
    assert names == ["rs_k8_m3_scrub_e0", "rs_k8_m3_degraded_e1",
                     "rs_k8_m3_degraded_e2_c1",
                     "rs_k8_m3_repair_batched_e1",
                     "rs_k8_m3_recovery_churn"]
    workloads = set()
    for _, extra in bench.DEGRADED_ROWS:
        args = bench.DEGRADED_COMMON + ["--iterations", "1"] + extra
        b = ErasureCodeBench()
        b.setup(args)                  # parses cleanly
        workloads.add(b.args.workload)
        e = b.args.erasures + b.args.corruptions
        assert e <= 3                  # m=3 budget
    assert workloads == {"degraded", "repair-batched",
                         "recovery-churn"}


def test_recovery_churn_workload():
    """--workload recovery-churn: the orchestrator heals --batch
    objects to byte-identical convergence while MapChurn advances the
    map every --churn-every dispatches; the row proves the fencing
    ran (epochs advanced, replans/regroups counted) and the batching
    held on the host path (zero device calls)."""
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "4",
                     "--iterations", "1",
                     "--workload", "recovery-churn", "--erasures", "1",
                     "--churn-every", "2", "--device", "host"])
    assert res["workload"] == "recovery-churn"
    assert res["gbps"] > 0
    assert res["epochs_advanced"] >= 1
    assert res["replans"] + res["regroups"] >= 1
    assert res["device_calls"] == 0        # --device host
    assert res["pattern_batches"] >= 1


def test_recovery_churn_workload_rejects_zero_erasures():
    with pytest.raises(ValueError, match="erasures"):
        run_bench(["--plugin", "jerasure",
                   "--parameter", "k=2", "--parameter", "m=1",
                   "--size", "4096", "--workload", "recovery-churn",
                   "--erasures", "0", "--device", "host"])


def test_repair_batched_workload():
    """The repair-batched workload heals --batch objects through the
    fused per-pattern device path and reports the batching proof
    (device calls == pattern batches, both far below object count)."""
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "6",
                     "--iterations", "1",
                     "--workload", "repair-batched", "--erasures", "1",
                     "--device", "jax"])
    assert res["workload"] == "repair-batched"
    assert res["gbps"] > 0
    assert res["pattern_batches"] >= 1
    assert res["device_calls"] + res["host_batches"] \
        == res["pattern_batches"]
    assert res["pattern_batches"] <= 4 < 6  # grouped, not per-object


def test_repair_batched_workload_host_pin():
    """--device host keeps the whole row on the grouped host path —
    zero jax dispatches."""
    res = run_bench(["--plugin", "jerasure",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "4",
                     "--iterations", "1",
                     "--workload", "repair-batched", "--erasures", "1",
                     "--device", "host"])
    assert res["device_calls"] == 0
    assert res["host_batches"] == res["pattern_batches"] >= 1


def test_bench_metric_version_and_slice_field():
    """Headline hygiene: the line carries the metric_version marker,
    every declared section has rows, and the headline value comes from
    the carry-chain candidates while the slice-chain number rides in
    the separate slice_gbps field."""
    import bench
    # metric_version 18: no host-only error line, topology names the
    # device kind, the native baseline is null when not measured
    assert bench.METRIC_VERSION == 18
    assert "detcheck" in bench._audit_meta()
    assert "lockcheck" in bench._audit_meta()
    assert "tenant_week_isolation" in dict(bench.TENANT_WEEK_ROWS)
    assert "victim_gbps_under_slo" in bench.TENANT_WEEK_ROW_FIELDS
    # one serving row: the dense shape buckets are the only path
    assert [name for name, _ in bench.SERVING_ROWS] == [
        "serving_mixed_closed"]
    assert "tail_attribution" in bench.SCENARIO_ROW_FIELDS
    assert "tail_attribution" in bench.SERVING_ROW_FIELDS
    for rows in (bench.HOST_CHAOS_ROWS, bench.AUTOTUNE_ROWS,
                 bench.DEVICE_CHAOS_ROWS, bench.SCENARIO_ROWS,
                 bench.PROFILE_ROWS, bench.SERVING_ROWS,
                 bench.CLUSTER_ROWS, bench.MULTICHIP_ROWS):
        assert dict(rows)  # at least one declared row
    topo = bench._topology({"platform": "tpu",
                            "device_kind": "TPU v5 lite",
                            "device_count": 4})
    assert topo == {"platform": "tpu", "device_kind": "TPU v5 lite",
                    "device_count": 4, "mesh_shape": None}
    # and bench rows are {gbps, lat_*} dicts (per-stripe-batch
    # latency percentiles alongside GB/s)
    row = bench._row_result({"gbps": 1.23456789, "lat_p50_ms": 0.5,
                             "lat_p99_ms": 0.9, "lat_p999_ms": 1.0,
                             "lat_samples": 7})
    # metric_version 11: every row carries its config provenance
    # (absent fields default to the hand-picked-constants regime)
    assert row == {"gbps": 1.2346, "lat_p50_ms": 0.5,
                   "lat_p99_ms": 0.9, "lat_p999_ms": 1.0,
                   "lat_samples": 7, "config_source": "default",
                   "tune_key_hash": None}
    # the official decode rows route shec through the packed slice
    # chain and clay through packed carry (MXU composites are not
    # DCE-opaque, so slice would be fiction there)
    rows = dict(bench.DECODE_ROWS)
    assert "slice" in rows["shec_k6_m3_c2_e1"]
    assert "packed" in rows["shec_k6_m3_c2_e1"]
    assert "carry" in rows["clay_k8_m4_d11_e1"]
    assert "packed" in rows["clay_k8_m4_d11_e1"]


def test_bench_metadata_records_audit_coverage():
    """Every emitted line records which code shapes were certified:
    the tpu-audit registry size and trace-rule ids (ISSUE 5)."""
    import bench
    meta = bench._audit_meta()
    assert meta["audited_entrypoints"] >= 12
    assert meta["audit_rules"] == sorted([
        "audit-float-lane", "audit-callback", "audit-transfer",
        "audit-weak-type", "audit-primitive-allowlist"])


def test_bench_section_records_failed_rows_as_null(monkeypatch, capsys):
    import bench

    def run(argv):
        if "bad" in argv:
            raise RuntimeError("boom")
        return {"gbps": 2.0, "verified": True}

    monkeypatch.setattr(bench, "_run", run)
    out = bench._section("demo", [("ok", ["good"]), ("ko", ["bad"])],
                         ("verified",))
    assert out["ok"]["gbps"] == 2.0 and out["ok"]["verified"] is True
    assert out["ko"] is None
    assert "demo/ko: RuntimeError: boom" in capsys.readouterr().err


def test_bench_exits_nonzero_when_every_device_run_fails(monkeypatch,
                                                         capsys):
    """A TPU that is present but whose every encode run fails is a
    failed measurement: nonzero exit, no result line (the host-only
    error line and its embedded last-good record are gone)."""
    import bench
    import ceph_tpu.utils.compile_cache as cc

    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "device_count": 1})
    monkeypatch.setattr(cc, "initialize_compile_cache", lambda: "x")

    def run(argv):
        if "jax" in argv:
            raise RuntimeError("device lost")
        return {"gbps": 1.0}

    monkeypatch.setattr(bench, "_run", run)
    monkeypatch.setattr(bench, "_cpp_baseline",
                        lambda: (None, "not measured"))
    assert bench.main() == 1
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "every device encode run failed" in cap.err


def test_bench_script_exits_nonzero_without_tpu():
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "bench.py"], cwd=repo,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 1
    assert r.stdout == ""
    assert "no TPU device" in r.stderr


def test_cpp_baseline_not_measured_without_binary(monkeypatch, tmp_path):
    import bench
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    assert bench._cpp_baseline() == (None, "not measured")


def test_multichip_workload_simulated_mesh():
    """--workload multichip (metric_version 5): encode sharded over
    the 8-device virtual CPU mesh through the engine's sharded serving
    program — byte-verified in-workload against the single-device
    engine, per-device stripe partition reported."""
    res = run_bench(["--workload", "multichip", "--plugin", "jerasure",
                     "--parameter", "technique=reed_sol_van",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "8192", "--batch", "16",
                     "--iterations", "2"])
    assert res["workload"] == "multichip"
    assert res["verified"] is True
    assert res["n_devices"] == 8
    assert res["mesh_shape"] == [8, 1]
    assert res["stripes_per_device"] == [2] * 8
    assert res["platform"] == "cpu"
    assert res["device_count"] == 8
    assert res["gbps"] > 0
    assert res["lat_samples"] == 2


def test_multichip_workload_rejects_host_device():
    with pytest.raises(SystemExit):
        run_bench(["--workload", "multichip", "--device", "host",
                   "--size", "4096"])


def test_cluster_workload_host():
    """--workload cluster (metric_version 6): the seeded storm →
    balance → rateless-recover scenario over a synthetic cluster —
    storm equivalence and byte-identical heal verified in-workload,
    remap convergence / balancer / p99-vs-control fields reported."""
    res = run_bench(["--workload", "cluster", "--plugin", "jerasure",
                     "--parameter", "technique=reed_sol_van",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "4096", "--batch", "4",
                     "--osds", "60", "--cluster-pgs", "64",
                     "--storm-events", "4", "--device", "host",
                     "--seed", "11"])
    assert res["workload"] == "cluster"
    assert res["verified"] is True
    assert res["engine"] == "host"
    assert res["osds"] >= 60
    for f in ("remap_convergence_epochs", "mean_remap_fraction",
              "balancer_iterations", "balancer_max_dev_final",
              "p99_recovery_ms", "p99_baseline_ms",
              "straggler_reassignments", "redundancy"):
        assert f in res, f
    assert res["storm_events"] >= 4
    assert res["balancer_iterations"] >= 1
    assert res["p99_recovery_ms"] > 0
    if res["p99_ratio"] is not None:
        # the rateless bound: 10x straggler, r=2 -> within 2x control
        assert res["p99_ratio"] <= 2.0


def test_serving_workload_host():
    """--workload serving (metric_version 4): the seeded mixed stream
    through the continuous batcher reports GB/s-under-SLO, request
    latency percentiles, deadline-miss rate and padding overhead —
    and byte-verifies every served request inside the workload."""
    res = run_bench(["--workload", "serving", "--requests", "24",
                     "--size", "4096", "--device", "host",
                     "--seed", "7"])
    assert res["workload"] == "serving"
    assert res["requests"] == 24
    assert res["gbps"] > 0
    for f in ("gbps_under_slo", "deadline_miss_rate",
              "padding_overhead", "lat_p50_ms", "lat_p99_ms",
              "lat_p999_ms", "rejected", "dispatches"):
        assert f in res, f
    assert res["lat_samples"] == 24
    assert 0.0 <= res["deadline_miss_rate"] <= 1.0
    assert 0.0 <= res["padding_overhead"] < 1.0
    # host executor never dispatches jax, so no compile accounting
    assert res["stream_compiles"] is None
    assert set(res["op_classes"]) <= {"encode", "decode", "repair"}


def test_profile_workload_device():
    """--workload profile (metric_version 7): the device-plane
    profiler drives the engine's cached programs and emits per-program
    attribution rows joining XLA cost_analysis with measured dispatch
    latency — bytes, FLOPs, p50, achieved GB/s and roofline
    utilization per (plugin, pattern, engine tier, device count)."""
    from ceph_tpu.telemetry import ProgramProfiler, set_global_profiler
    prev = set_global_profiler(ProgramProfiler())
    try:
        res = run_bench(["--workload", "profile", "--plugin", "jerasure",
                         "--parameter", "technique=reed_sol_van",
                         "--parameter", "k=4", "--parameter", "m=2",
                         "--size", "8192", "--batch", "4",
                         "--iterations", "2", "-e", "1"])
    finally:
        set_global_profiler(prev)
    assert res["workload"] == "profile"
    # serve-encode + serve-decode + fused-repair, one row each
    assert res["programs"] == 3
    kinds = sorted(r["kind"] for r in res["profile_rows"])
    assert kinds == ["fused-repair", "serve-decode", "serve-encode"]
    for row in res["profile_rows"]:
        assert row["source"] == "xla"
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert row["arg_bytes"] > 0
        assert row["calls"] >= 3          # warm + 2 timed iterations
        assert row["p50_ms"] > 0
        assert row["achieved_gbps"] > 0 and row["hbm_gbps"] > 0
        # the CPU has no roofline peak: utilization "not measured"
        assert row["device_kind"] == "cpu"
        assert row["utilization_pct"] is None
        assert row["pattern"].startswith("e")
    assert res["gbps"] > 0 and res["lat_samples"] == 6


def test_profile_workload_host_analytic():
    """--workload profile --device host: no jax anywhere — the cost side comes from the analytic GF(2^8)
    matrix model with honest source="analytic" provenance, the
    measured side from the numpy batch surfaces."""
    res = run_bench(["--workload", "profile", "--plugin", "jerasure",
                     "--parameter", "technique=reed_sol_van",
                     "--parameter", "k=4", "--parameter", "m=2",
                     "--size", "8192", "--batch", "2",
                     "--iterations", "2", "-e", "1",
                     "--device", "host"])
    assert res["programs"] == 2           # host encode + host decode
    for row in res["profile_rows"]:
        assert row["source"] == "analytic"
        # decode rows whose pattern matrix the XOR-density probe
        # schedules carry engine="xor" with the schedule's real op
        # count (ISSUE 12 — the analytic model extended to XOR
        # schedules); everything else stays "host"
        assert row["engine"] in ("host", "xor")
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert row["p50_ms"] > 0 and row["achieved_gbps"] > 0
    assert res["gbps"] > 0
