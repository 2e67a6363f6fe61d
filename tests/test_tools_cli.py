"""CLI surface tests for the osdmaptool and ceph_erasure_code analogs
(ceph_tpu/bench/osdmaptool.py, erasure_code_tool.py)."""

import json
import pathlib
import subprocess
import sys

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])


def run(mod, *args):
    return subprocess.run([sys.executable, "-m", mod, *args],
                          capture_output=True, text=True, cwd=REPO_ROOT)


def test_erasure_code_tool_plugin_exists():
    r = run("ceph_tpu.bench.erasure_code_tool", "--plugin_exists",
            "jerasure")
    assert r.returncode == 0 and "exists" in r.stdout
    r = run("ceph_tpu.bench.erasure_code_tool", "--plugin_exists",
            "nonesuch")
    assert r.returncode == 1


def test_erasure_code_tool_profile_roundtrip():
    r = run("ceph_tpu.bench.erasure_code_tool", "--plugin", "jerasure",
            "--parameter", "k=4", "--parameter", "m=2",
            "--parameter", "technique=reed_sol_van", "--all")
    assert r.returncode == 0, r.stderr
    assert "k=4 m=2" in r.stdout and "round-trip ok" in r.stdout


def test_erasure_code_tool_bad_profile():
    r = run("ceph_tpu.bench.erasure_code_tool", "--plugin", "jerasure",
            "--parameter", "k=1", "--parameter", "m=2")
    assert r.returncode == 1 and "failed to initialize" in r.stderr


def test_osdmaptool_createsimple_testmappgs_upmap(tmp_path):
    mapfn = str(tmp_path / "map.json")
    r = run("ceph_tpu.bench.osdmaptool", "--createsimple", "6",
            "--pg-num", "64", "-o", mapfn)
    assert r.returncode == 0, r.stderr
    spec = json.load(open(mapfn))
    assert spec["pools"][0]["pg_num"] == 64

    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs",
            "--engine", "host")
    assert r.returncode == 0, r.stderr
    assert "mapped 64 pgs" in r.stdout and "osd.0" in r.stdout

    outfn = str(tmp_path / "upmaps.sh")
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--upmap", outfn,
            "--upmap-deviation", "0.5", "--engine", "host")
    assert r.returncode == 0, r.stderr
    cmds = open(outfn).read().strip().splitlines()
    assert all(c.startswith("ceph osd pg-upmap-items 1.") for c in cmds)


def test_osdmaptool_overrides_affect_mapping(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4",
        "--pg-num", "32", "-o", mapfn)
    spec = json.load(open(mapfn))
    spec["osd_out"] = [0]
    spec["osd_down"] = [0]
    json.dump(spec, open(mapfn, "w"))
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs",
            "--engine", "host")
    assert r.returncode == 0, r.stderr
    assert "osd.0\t0" in r.stdout       # out+down osd takes nothing


def test_crushtool_show_utilization():
    r = run("ceph_tpu.bench.crushtool", "--build-two-level", "3", "2",
            "--test", "--engine", "host", "--max-x", "199",
            "--show-utilization")
    assert r.returncode == 0, r.stderr
    lines = [l for l in r.stdout.splitlines() if "stored" in l]
    assert len(lines) == 6
    stored = sum(int(l.split()[3]) for l in lines)
    assert stored == 200 * 3           # every placement accounted for


def test_osdmaptool_requires_action(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "3", "-o", mapfn)
    r = run("ceph_tpu.bench.osdmaptool", mapfn)
    assert r.returncode == 2


def test_osdmaptool_unknown_pool_field_clean_error(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "3", "-o", mapfn)
    spec = json.load(open(mapfn))
    spec["pools"][0]["bogus_field"] = 1
    json.dump(spec, open(mapfn, "w"))
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs")
    assert r.returncode != 0
    assert "unknown pool field" in r.stderr and "bogus_field" in r.stderr
    assert "Traceback" not in r.stderr


def test_osdmaptool_dump_preserves_overrides(tmp_path):
    """dump_osdmap must round-trip the override layers (reweight, down,
    out, affinity, upmap items) so editing a dumped map doesn't lose
    state."""
    from ceph_tpu.bench.osdmaptool import dump_osdmap, load_osdmap
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4",
        "--pg-num", "32", "-o", mapfn)
    m = load_osdmap(mapfn)
    m.osd_weight[1] = 32768                 # reweight 0.5
    m.mark_down(2)
    m.mark_out(3)
    m.set_primary_affinity(0, 0)
    m.pg_upmap_items[(1, 5)] = [(0, 1)]
    dumped = str(tmp_path / "dumped.json")
    json.dump(dump_osdmap(m, list(m.pools.values())), open(dumped, "w"))
    m2 = load_osdmap(dumped)
    assert m2.osd_weight[1] == 32768
    assert not m2.osd_up[2]
    assert m2.osd_weight[3] == 0
    assert m2.osd_primary_affinity[0] == 0
    assert m2.pg_upmap_items[(1, 5)] == [(0, 1)]


def test_osdmaptool_summary_counts_empty_in_osds(tmp_path):
    """An in-but-empty osd belongs in the --test-map-pgs summary: min
    must be able to reach 0 (the imbalance the sweep exists to show),
    and the header precedes the per-osd rows."""
    from ceph_tpu.bench.osdmaptool import dump_osdmap, load_osdmap
    from ceph_tpu.crush.types import CRUSH_ITEM_NONE
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4",
        "--pg-num", "8", "-o", mapfn)
    m = load_osdmap(mapfn)
    pool = m.pools[1]
    # drain osd 2 completely: for each pg holding it, upmap its
    # replica to the one osd not already in the pg (4 osds, size 3)
    for ps in range(pool.pg_num):
        up, _, _, _ = m.pg_to_up_acting_osds(1, ps)
        members = [o for o in up if o != CRUSH_ITEM_NONE]
        if 2 in members:
            free = next(o for o in range(4) if o not in members)
            m.pg_upmap_items[(1, pool.raw_pg_to_pg(ps))] = [(2, free)]
    json.dump(dump_osdmap(m, [pool]), open(mapfn, "w"))
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs",
            "--engine", "host")
    assert r.returncode == 0, r.stderr
    out = r.stdout.splitlines()
    hdr = next(i for i, l in enumerate(out) if l.startswith("#osd"))
    rows = next(i for i, l in enumerate(out) if l.startswith("osd.0"))
    assert hdr < rows                       # header before rows
    osd2 = next(l for l in out if l.startswith("osd.2"))
    assert osd2.split("\t")[1] == "0", f"osd 2 not drained: {osd2}"
    assert " min 0 " in r.stdout


def test_osdmaptool_dump_preserves_pool_shape_fields(tmp_path):
    """pgp_num (mid-split), min_size, hashpspool survive a dump/load
    round-trip — pgp_num feeds raw_pg_to_pps, so dropping it silently
    remaps every pg."""
    from ceph_tpu.bench.osdmaptool import dump_osdmap, load_osdmap
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4",
        "--pg-num", "32", "-o", mapfn)
    m = load_osdmap(mapfn)
    m.pools[1].pgp_num = 16
    m.pools[1].min_size = 1
    m.pools[1].hashpspool = False
    dumped = str(tmp_path / "dumped.json")
    json.dump(dump_osdmap(m, list(m.pools.values())), open(dumped, "w"))
    m2 = load_osdmap(dumped)
    assert m2.pools[1].pgp_num == 16
    assert m2.pools[1].min_size == 1
    assert m2.pools[1].hashpspool is False


def test_osdmaptool_missing_pool_field_clean_error(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "3", "-o", mapfn)
    spec = json.load(open(mapfn))
    del spec["pools"][0]["pg_num"]
    json.dump(spec, open(mapfn, "w"))
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs")
    assert r.returncode != 0
    assert "missing required" in r.stderr and "Traceback" not in r.stderr


def test_osdmaptool_create_ec_pool(tmp_path):
    """profile -> rule -> pool via the CLI (mon-analog flow), then the
    created pool places through --test-map-pgs."""
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "8",
        "--pg-num", "16", "-o", mapfn)
    r = run("ceph_tpu.bench.osdmaptool", mapfn,
            "--create-ec-pool", "ecprof",
            "--ec-profile", "plugin=jerasure",
            "--ec-profile", "technique=reed_sol_van",
            "--ec-profile", "k=4", "--ec-profile", "m=2",
            "--ec-profile", "crush-failure-domain=host",
            "--ec-profile", "crush-root=root",
            "--pg-num", "32")
    assert r.returncode == 0, r.stderr
    assert "size=6 min_size=5" in r.stdout
    spec = json.load(open(mapfn))
    ec_pools = [p for p in spec["pools"] if p["erasure"]]
    assert len(ec_pools) == 1 and ec_pools[0]["size"] == 6
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--test-map-pgs",
            "--pool", str(ec_pools[0]["pool_id"]), "--engine", "host")
    assert r.returncode == 0, r.stderr
    assert "mapped 32 pgs" in r.stdout


def test_osdmaptool_create_ec_pool_bad_profile_clean(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4", "-o", mapfn)
    r = run("ceph_tpu.bench.osdmaptool", mapfn,
            "--create-ec-pool", "bad",
            "--ec-profile", "plugin=jerasure", "--ec-profile", "k=1",
            "--ec-profile", "m=2")
    assert r.returncode != 0
    assert "Traceback" not in r.stderr


def test_osdmaptool_create_ec_pool_unknown_plugin_clean(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4", "-o", mapfn)
    r = run("ceph_tpu.bench.osdmaptool", mapfn,
            "--create-ec-pool", "x", "--ec-profile", "plugin=nope")
    assert r.returncode != 0 and "Traceback" not in r.stderr
    assert "--create-ec-pool" in r.stderr


def test_osdmaptool_create_ec_pool_refuses_duplicate_id(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4", "-o", mapfn)
    r = run("ceph_tpu.bench.osdmaptool", mapfn,
            "--create-ec-pool", "p", "--pool-id", "1",
            "--ec-profile", "plugin=jerasure",
            "--ec-profile", "technique=reed_sol_van",
            "--ec-profile", "k=4", "--ec-profile", "m=2",
            "--ec-profile", "crush-root=root",
            "--ec-profile", "crush-failure-domain=host")
    assert r.returncode != 0 and "already exists" in r.stderr
    # the original pool survives untouched
    spec = json.load(open(mapfn))
    assert spec["pools"][0]["erasure"] is False


def test_osdmaptool_print(tmp_path):
    mapfn = str(tmp_path / "map.json")
    run("ceph_tpu.bench.osdmaptool", "--createsimple", "4",
        "--pg-num", "16", "-o", mapfn)
    spec = json.load(open(mapfn))
    spec["osd_out"] = [2]
    spec["osd_down"] = [2]
    json.dump(spec, open(mapfn, "w"))
    r = run("ceph_tpu.bench.osdmaptool", mapfn, "--print")
    assert r.returncode == 0, r.stderr
    assert "epoch 0" in r.stdout and "max_osd 4" in r.stdout
    assert "pool 1 'replicated' size 3" in r.stdout
    assert "osd.2 down out weight 0" in r.stdout
    assert "osd.0 up in weight 1" in r.stdout


def test_crushtool_edit_surface(tmp_path):
    """--add-item / --reweight-item / --remove-item (CrushWrapper
    insert/adjust/remove through the CLI), round-tripped through the
    text form and verified by a --test sweep."""
    mapfn = str(tmp_path / "m.txt")
    run("ceph_tpu.bench.crushtool", "--build-two-level", "3", "2",
        "-o", mapfn)
    # add osd.6 into host1
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "6", "2.0", "osd.6",
            "--loc", "host", "host1", "-o", mapfn)
    assert r.returncode == 0, r.stderr
    assert "osd.6" in open(mapfn).read()
    # reweight it
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--reweight-item", "osd.6", "0.5", "-o", mapfn)
    assert r.returncode == 0, r.stderr
    assert "0.5" in open(mapfn).read()
    # placement still works and uses the new device
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn, "--test",
            "--engine", "host", "--max-x", "299", "--show-utilization")
    assert r.returncode == 0, r.stderr
    assert "device 6" in r.stdout
    # remove it again
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--remove-item", "osd.6", "-o", mapfn)
    assert r.returncode == 0, r.stderr
    assert "osd.6" not in open(mapfn).read()
    # bad location type is a clean error
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "7", "1.0", "osd.7", "--loc", "rack", "host0")
    assert r.returncode != 0 and "Traceback" not in r.stderr


def test_scrub_demo_recoverable_and_unrecoverable():
    """tools/scrub_demo.py: the chaos→scrub→repair→remap CLI — rc 0 +
    healed report under budget, rc 2 + structured unrecoverable report
    past it (the same gates tools/test_full.sh enforces)."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "scrub_demo.py")
    r = subprocess.run([sys.executable, script, "--erasures", "1",
                        "--corruptions", "1", "--transient", "2",
                        "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["repair"]["healed"] is True
    assert out["repair"]["reencode_verified"] is True
    assert out["scrub"]["retried_shards"]      # transient path hit
    assert out["remap"]["marked_osds"]
    assert set(out["remap"]["moved"])          # bad slots re-homed

    r = subprocess.run([sys.executable, script, "--erasures", "3",
                        "--corruptions", "1", "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 2, r.stderr
    out = json.loads(r.stdout)
    assert len(out["unrecoverable"]["shards"]) == 4
    assert out["unrecoverable"]["extents"]


def test_crushtool_add_item_validation(tmp_path):
    """Duplicate ids/names and device locations are rejected cleanly
    (CrushWrapper::insert_item semantics), and an --add-item is visible
    to a --reweight-item in the SAME invocation."""
    mapfn = str(tmp_path / "m.txt")
    run("ceph_tpu.bench.crushtool", "--build-two-level", "3", "2",
        "-o", mapfn)
    # duplicate id
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "0", "1.0", "osd.x", "--loc", "host", "host1")
    assert r.returncode != 0 and "already exists" in r.stderr
    # duplicate name
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "9", "1.0", "host0", "--loc", "host", "host1")
    assert r.returncode != 0 and "already used" in r.stderr
    # device as location
    run("ceph_tpu.bench.crushtool", "-i", mapfn,
        "--add-item", "9", "1.0", "osd.9", "--loc", "host", "host1",
        "-o", mapfn)
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "10", "1.0", "osd.10", "--loc", "osd", "osd.9")
    assert r.returncode != 0 and "device, not a bucket" in r.stderr
    # add + reweight in one invocation
    r = run("ceph_tpu.bench.crushtool", "-i", mapfn,
            "--add-item", "11", "1.0", "osd.11", "--loc", "host", "host2",
            "--reweight-item", "osd.11", "2.0", "-o", mapfn)
    assert r.returncode == 0, r.stderr
    assert "reweight_item osd.11" in r.stderr

def test_recovery_demo_churn_crash_torn():
    """tools/recovery_demo.py: the churn+crash+torn recovery CLI — rc 0
    with a converged byte-identical report under budget, rc 2 with the
    structured unrecoverable report past it (the same gates
    tools/test_full.sh enforces)."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "recovery_demo.py")
    r = subprocess.run([sys.executable, script, "--erasures", "1",
                        "--corruptions", "1", "--churn", "3",
                        "--crash-site", "writeback.after_write",
                        "--torn", "--objects", "4", "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["byte_identical"] is True
    assert out["report"]["converged"] is True
    assert out["report"]["crashes"] == 1
    assert out["report"]["journal"]["replays"] >= 2   # boot + resume
    assert out["churn_events"]

    # past the m=2 budget: structured unrecoverable report, rc 2
    r = subprocess.run([sys.executable, script, "--erasures", "3",
                        "--churn", "0", "--objects", "2", "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 2, r.stderr
    out = json.loads(r.stdout)
    assert out["report"]["unrecoverable"]
    assert out["byte_identical"] is True    # survivors still intact


def test_recovery_demo_list_sites():
    import os
    from ceph_tpu.chaos import CRASH_SITES
    script = os.path.join(REPO_ROOT, "tools", "recovery_demo.py")
    r = subprocess.run([sys.executable, script, "--list-sites"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0
    assert tuple(r.stdout.split()) == CRASH_SITES


def test_perf_dump_cli_deterministic_and_valid():
    """tools/perf_dump.py (docs/OBSERVABILITY.md): the seeded repair
    scenario under --fake-clock emits a schema-valid unified dump
    that is BYTE-identical across runs, and --format prom emits
    Prometheus text exposition for the same registry."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "perf_dump.py")

    def dump_run():
        return subprocess.run(
            [sys.executable, script, "--scenario", "repair",
             "--fake-clock", "--validate", "--format", "json"],
            capture_output=True, text=True, cwd=REPO_ROOT)

    r1, r2 = dump_run(), dump_run()
    assert r1.returncode == 0, r1.stderr
    assert r1.stdout == r2.stdout          # byte-identical dump
    dump = json.loads(r1.stdout)
    tel = dump["ceph_tpu_telemetry"]
    assert tel["chaos_injections{kind=erase}"] >= 1
    assert dump["spans"]["spans"][0]["name"] == "repair"

    r = subprocess.run(
        [sys.executable, script, "--scenario", "repair",
         "--fake-clock", "--format", "prom"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr
    assert "ceph_tpu_telemetry_scrub_dispatch_seconds" in r.stdout
    assert "_total" in r.stdout and "quantile=" in r.stdout


def test_serve_demo_recoverable_and_unrecoverable():
    """tools/serve_demo.py: the seeded serving scenario CLI — rc 0
    with a byte-verified stream, chaos-degraded repair slice and a
    schema-valid telemetry dump; rc 2 with the structured report when
    the erasure budget exceeds every code's decode capability (the
    same gates tools/test_full.sh enforces)."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "serve_demo.py")
    r = subprocess.run([sys.executable, script, "--requests", "32",
                        "--validate", "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["requests"] == 32
    assert out["corrupted"] == []
    assert out["verified"] == 32
    assert out["degraded_repairs"] >= 1          # chaos slice exercised
    assert out["telemetry_schema_errors"] == []
    assert out["padding"]["dispatches"] == len(out["dispatches"])

    r = subprocess.run([sys.executable, script, "--erasures", "4",
                        "--json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 2, r.stderr
    out = json.loads(r.stdout)
    assert out["unrecoverable"] is True
    assert "erasure" in out["error"] or "decodable" in out["error"]


def test_perf_dump_flight_recorder_deterministic_and_valid():
    """tools/perf_dump.py --scenario unrecoverable --flight-recorder
    (ISSUE 10): the seeded past-budget repair freezes a flight-
    recorder post-mortem whose dump — ring, spans, metrics snapshot,
    deltas — is schema-valid (v2) and BYTE-identical across reruns
    under --fake-clock."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "perf_dump.py")

    def dump_run():
        return subprocess.run(
            [sys.executable, script, "--scenario", "unrecoverable",
             "--fake-clock", "--flight-recorder", "--validate"],
            capture_output=True, text=True, cwd=REPO_ROOT)

    r1, r2 = dump_run(), dump_run()
    assert r1.returncode == 0, r1.stderr
    assert r1.stdout == r2.stdout          # byte-identical
    dump = json.loads(r1.stdout)
    fr = dump["flight_recorder"]
    assert fr["dump_count"] >= 1
    blob = fr["dumps"][-1]
    assert blob["trigger"] == "unrecoverable"
    assert "failure budget" in blob["reason"]
    assert blob["metrics_delta"]           # counters moved before death


def test_perf_dump_profile_filtered_deterministic():
    """tools/perf_dump.py --profile (ISSUE 10): attribution rows with
    cost + measured + roofline fields, deterministic under
    --fake-clock (the measured side rides a tick clock).  Filtered to
    the engine/serve entries to keep the test fast — the full
    every-jit-entry coverage gate runs in tools/test_full.sh."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "perf_dump.py")

    def profile_run():
        return subprocess.run(
            [sys.executable, script, "--scenario", "none", "--profile",
             "--profile-filter", "engine.fused_repair_call",
             "--profile-filter", "serve.dispatch",
             "--fake-clock", "--validate"],
            capture_output=True, text=True, cwd=REPO_ROOT)

    r1, r2 = profile_run(), profile_run()
    assert r1.returncode == 0, r1.stderr
    assert r1.stdout == r2.stdout          # byte-identical rows
    dump = json.loads(r1.stdout)
    prof = dump["profile"]
    assert prof["programs"] >= 2
    for row in prof["rows"]:
        if row["kind"] != "entrypoint":
            continue
        assert row["flops"] is not None
        assert row["bytes_accessed"] > 0
        assert row["p50_ms"] > 0
        assert row["achieved_gbps"] > 0
        # a CPU run has no roofline peak: "not measured"
        assert row["utilization_pct"] is None
    assert prof["top"]                     # hot list populated


def test_bench_diff_cli_red_and_green(tmp_path):
    """tools/bench_diff.py (ISSUE 10): rc 4 + REGRESSION line on a
    synthetic 20% headline drop, rc 0 on the repo itself (no
    trajectory: no baseline)."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": {
            "metric": "m", "value": 100.0, "git_sha": "aaa",
            "timestamp": "2026-01-01T00:00:00+00:00"}}))
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(
        {"metric": "m", "value": 80.0, "git_sha": "bbb",
         "timestamp": "2026-02-01T00:00:00+00:00"}))
    r = subprocess.run([sys.executable, script, "--repo",
                        str(tmp_path)],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 4, r.stdout
    assert "REGRESSION" in r.stderr and "headline" in r.stderr

    r = subprocess.run([sys.executable, script],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_diff_cli_composite_decode_red(tmp_path):
    """The composite_decode category (ISSUE 12): a 30% shec decode-row
    drop trips the sentinel under its own category name even while
    the headline and the RS decode row hold steady — the gap the
    XOR-scheduled kernels closed can never silently reopen."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": {
            "metric": "m", "value": 100.0, "git_sha": "aaa",
            "timestamp": "2026-01-01T00:00:00+00:00",
            "decode_rows": {"rs_k8_m3_e2": 140.0,
                            "shec_k6_m3_c2_e1": {"gbps": 100.0},
                            "clay_k8_m4_d11_e1": {"gbps": 50.0}}}}))
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(
        {"metric": "m", "value": 100.0, "git_sha": "bbb",
         "timestamp": "2026-02-01T00:00:00+00:00",
         "decode_rows": {"rs_k8_m3_e2": {"gbps": 140.0},
                         "shec_k6_m3_c2_e1": {"gbps": 70.0},
                         "clay_k8_m4_d11_e1": {"gbps": 50.0}}}))
    r = subprocess.run([sys.executable, script, "--repo",
                        str(tmp_path)],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 4, r.stdout
    assert "composite_decode:shec_k6_m3_c2_e1" in r.stderr
    assert "rs_k8_m3_e2" not in r.stderr
