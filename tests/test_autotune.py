"""Roofline-closing autotuner (ISSUE 14): the best-config-table
lifecycle, the consultation seams' fallback byte-identity, the
zero-warm-recompile contract under a tuned table, and the seeded
analytic sweep's determinism.

What must hold forever:

- the table round-trips and is schema-versioned; a mismatched
  ``table_schema_version`` refuses to load;
- the staleness guard ignores entries stamped with another
  platform/device_count/jax_version — counted (``tune_config_stale``)
  and evented, never silent — so a table tuned on one topology can
  never mis-configure another;
- every consultation seam falls back to today's hand-picked constants
  BYTE-IDENTICALLY when the table is missing/stale/invalid, and a
  tuned table changes only where bytes are computed, never the bytes
  (all five plugin families, byte + packed layouts);
- programs built under a tuned table compile once and never again
  (armed recompile budget + compile counter == 0 on the warm path);
- the analytic sweep is a deterministic pure function of its seed.
"""

from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

from ceph_tpu.codes.registry import ErasureCodePluginRegistry
from ceph_tpu.tune import sweep as tsweep
from ceph_tpu.tune import table as ttable
from ceph_tpu.tune.table import (BestConfigTable, current_env, key_hash,
                                 key_str, parse_key, tuning_key,
                                 validate_table)

REPO_ROOT = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(autouse=True)
def _clean_table():
    """Every test starts (and leaves the process) with NO table
    installed — the consultation seams must default cleanly."""
    prev = ttable.install_table(None)
    yield
    ttable.install_table(prev)


def _fresh_metrics():
    from ceph_tpu.telemetry.metrics import (MetricsRegistry,
                                            set_global_metrics)
    reg = MetricsRegistry()
    prev = set_global_metrics(reg)
    return reg, prev


# ----------------------------------------------------------------------
# table lifecycle: keys, round-trip, schema versioning


def test_tuning_key_roundtrip_and_hash():
    key = tuning_key("jerasure:k=8,m=3", "serve-encode", "device",
                     "packed", 8, 16)
    assert parse_key(key_str(key)) == key
    assert len(key_hash(key)) == 12
    with pytest.raises(ValueError):
        tuning_key(kind="")
    with pytest.raises(ValueError):
        parse_key("too|few|slots")


def test_table_roundtrip_save_load(tmp_path):
    t = BestConfigTable()
    k1 = tuning_key("*", "serve-ladder", "*", "*", 1, 0)
    k2 = tuning_key("m:abc", "matrix-engine", "*", "bytes", 1, 0)
    t.set(k1, {"ladder": [1, 2, 8]}, mode="analytic", score=0.5,
          baseline_score=0.7, baseline_config={"ladder": [1, 4, 16, 64]})
    t.set(k2, {"engine": "xor"}, mode="timed")
    assert validate_table(t.to_dict()) == []
    t2 = BestConfigTable.from_dict(t.to_dict())
    assert t2.to_json() == t.to_json()
    path = str(tmp_path / "table.json")
    t.save(path)
    t3 = BestConfigTable.load(path)
    assert t3.to_json() == t.to_json()
    assert t3.lookup(k1) == {"ladder": [1, 2, 8]}
    assert t.content_hash() == t3.content_hash()
    assert len(t.content_hash()) == 12


def test_table_schema_version_refused():
    t = BestConfigTable()
    t.set(tuning_key("*", "serve-ladder", "*", "*", 1, 0),
          {"ladder": [1, 2]}, mode="analytic")
    d = t.to_dict()
    d["table_schema_version"] = 999
    assert any("table_schema_version" in e for e in validate_table(d))
    with pytest.raises(ValueError):
        BestConfigTable.from_dict(d)


def test_validate_table_catches_bad_entries():
    good = BestConfigTable()
    good.set(tuning_key("*", "row-tile", "pallas", "bytes", 1, 0),
             {"max_row_tile8": 256}, mode="analytic")
    d = good.to_dict()
    d["entries"]["not|a|key"] = {"config": {}, "env": {},
                                 "mode": "analytic"}
    errors = validate_table(d)
    assert any("not|a|key" in e for e in errors)
    d2 = good.to_dict()
    ks = next(iter(d2["entries"]))
    d2["entries"][ks]["mode"] = "vibes"
    assert any("mode" in e for e in validate_table(d2))


# ----------------------------------------------------------------------
# staleness guard (ISSUE 14 satellite): mismatched topology entries
# are ignored, counted, and evented — never applied, never silent


def test_staleness_guard_counts_and_events():
    reg, prev = _fresh_metrics()
    try:
        now = current_env()
        stale_env = dict(now, platform="tpu-v9",
                         device_count=now["device_count"] + 64)
        t = BestConfigTable(env=stale_env)
        key = tuning_key("*", "serve-ladder", "*", "*",
                         stale_env["device_count"], 0)
        t.set(key, {"ladder": [1, 2]}, mode="timed")
        assert t.lookup(key) is None          # ignored, not applied
        assert t.lookup(key) is None
        assert reg.counter_value("tune_config_stale") == 2
        events = [e for e in reg._events
                  if e["event"] == "tune_config_stale"]
        assert len(events) == 1               # once per key, not per hit
        assert "platform" in events[0]["mismatched"]
    finally:
        from ceph_tpu.telemetry.metrics import set_global_metrics
        set_global_metrics(prev)


def test_fresh_entries_match_current_env():
    t = BestConfigTable()
    key = tuning_key("*", "xor-schedule", "*", "*",
                     current_env()["device_count"], 0)
    t.set(key, {"cse_topk": 64}, mode="analytic")
    assert t.lookup(key) == {"cse_topk": 64}


def test_consult_defaults_with_no_table():
    assert ttable.consult("serve-ladder") is None
    assert ttable.active_source() == ("default", None)
    from ceph_tpu.ops.pallas_gf import (mxu_matrix_min,
                                        tuned_row_tile_cap)
    from ceph_tpu.ops.xor_schedule import (tuned_cse_topk,
                                           tuned_xor_cutover)
    from ceph_tpu.serve.batcher import LADDER, tuned_ladder
    assert mxu_matrix_min() == 2048
    assert tuned_row_tile_cap(False) is None
    assert tuned_cse_topk() == 128
    assert tuned_xor_cutover() == (3, 4)
    assert tuned_ladder() == LADDER


def test_space_defaults_match_live_constants():
    """tune/space.py duplicates the hand-picked defaults as data —
    drift between the space and the live constants fails here, not in
    a user's sweep."""
    from ceph_tpu.ops.pallas_gf import MAX_ROW_TILE8, MXU_MATRIX_MIN
    from ceph_tpu.ops.xor_schedule import CSE_TOPK, XOR_DENSE_CUTOVER
    from ceph_tpu.serve.batcher import LADDER
    from ceph_tpu.tune.space import DEFAULTS, candidates, kinds
    assert DEFAULTS["row-tile"]["max_row_tile8"] == MAX_ROW_TILE8
    assert DEFAULTS["engine-select"]["mxu_matrix_min"] == MXU_MATRIX_MIN
    assert DEFAULTS["engine-select"]["xor_cutover"] == XOR_DENSE_CUTOVER
    assert DEFAULTS["xor-schedule"]["cse_topk"] == CSE_TOPK
    assert tuple(DEFAULTS["serve-ladder"]["ladder"]) == LADDER
    # the seams of the removed page-pool serving path have no kind
    assert {"stripe-pool", "ragged-cutover"}.isdisjoint(kinds())
    # every kind's default value is itself a candidate (the sweep can
    # never do worse than the status quo on its own model)
    for kind in kinds():
        if kind in ("mesh-fanout", "matrix-engine"):
            continue  # sentinel defaults (0 / None) are not swept
        assert DEFAULTS[kind] in list(candidates(kind))


# ----------------------------------------------------------------------
# consultation seams: tuned values honored, invalid values ignored


def _tuned_global_table(entries) -> BestConfigTable:
    t = BestConfigTable()
    dc = current_env()["device_count"]
    for (kind, engine, layout), config in entries.items():
        t.set(tuning_key("*", kind, engine, layout, dc, 0), config,
              mode="analytic")
    return t


def test_row_tile_cap_seam():
    from ceph_tpu.ops.pallas_gf import tuned_row_tile_cap
    t = _tuned_global_table(
        {("row-tile", "pallas", "bytes"): {"max_row_tile8": 256},
           ("row-tile", "pallas", "packed"): {"max_row_tile8": 100}})
    ttable.install_table(t)
    assert tuned_row_tile_cap(False) == 256
    assert tuned_row_tile_cap(True) is None   # 100 % 32 != 0: rejected


def test_row_tile_cap_byte_identity_interpret():
    """A tuned cap changes partitioning only: the interpret-mode
    Pallas kernel is byte-identical at any legal cap."""
    from ceph_tpu.ops.pallas_gf import apply_matrix_pallas
    rng = np.random.default_rng(5)
    ms = ((1, 1, 1, 1), (1, 2, 4, 8))
    x = rng.integers(0, 256, (2, 4, 128 * 128), dtype=np.uint8)
    ref = np.asarray(apply_matrix_pallas(x, ms, True))
    for cap in (32, 64, 512):
        out = np.asarray(apply_matrix_pallas(x, ms, True, cap))
        assert np.array_equal(out, ref), f"cap={cap} diverged"


def test_threshold_and_cutover_seams():
    from ceph_tpu.ops.pallas_gf import mxu_matrix_min
    from ceph_tpu.ops.xor_schedule import (tuned_cse_topk,
                                           tuned_xor_cutover)
    t = _tuned_global_table(
        {("engine-select", "*", "*"): {"mxu_matrix_min": 4096,
                                         "xor_cutover": [7, 8]},
           ("xor-schedule", "*", "*"): {"cse_topk": 64}})
    ttable.install_table(t)
    assert mxu_matrix_min() == 4096
    assert tuned_xor_cutover() == (7, 8)
    assert tuned_cse_topk() == 64
    # invalid values fall back, never raise
    t2 = _tuned_global_table(
        {("engine-select", "*", "*"): {"mxu_matrix_min": -3,
                                         "xor_cutover": "garbage"},
           ("xor-schedule", "*", "*"): {"cse_topk": True}})
    ttable.install_table(t2)
    assert mxu_matrix_min() == 2048
    assert tuned_xor_cutover() == (3, 4)
    assert tuned_cse_topk() == 128


def test_engine_pin_validated_against_backend():
    """A pin is honored only when dispatchable here: pallas/mxu pins
    are ignored on a CPU backend; xla pins apply; xor pins need a
    schedule."""
    from ceph_tpu.ops.pallas_gf import select_matrix_engine
    from ceph_tpu.tune.table import matrix_digest
    ms = ((1, 1, 1, 1), (1, 2, 4, 8))          # schedulable (xor wins)
    shape = (2, 4, 4096)
    default = select_matrix_engine(shape, ms, 8, engine="xla", mesh=0)
    t = BestConfigTable()
    t.set(tuning_key("m:" + matrix_digest(ms), "matrix-engine", "*",
                     "bytes", 1, 0), {"engine": "pallas"},
          mode="timed")
    ttable.install_table(t)
    assert select_matrix_engine(shape, ms, 8, engine="xla",
                                mesh=0) == default
    t2 = BestConfigTable()
    t2.set(tuning_key("m:" + matrix_digest(ms), "matrix-engine", "*",
                      "bytes", 1, 0), {"engine": "xla"}, mode="timed")
    ttable.install_table(t2)
    assert select_matrix_engine(shape, ms, 8, engine="xla",
                                mesh=0) == "xla"
    # numpy tier is never overridden by a pin (a pin cannot resurrect
    # a dead backend)
    assert select_matrix_engine(shape, ms, 8, engine="numpy",
                                mesh=0) == "numpy"


def test_engine_pin_xor_past_cutover_still_dispatches():
    """A measured xor pin may route PAST the cutover heuristic; the
    dispatch path must fall through to the raw schedule instead of
    asserting (the _xor_sched_static fallback)."""
    from ceph_tpu.ops.pallas_gf import (_run_matrix_bytes,
                                        select_matrix_engine)
    from ceph_tpu.ops.xor_schedule import (preferred_schedule,
                                           probe_schedule)
    from ceph_tpu.tune.table import matrix_digest
    # the jerasure RS k4m2 matrix: schedulable but the cutover
    # usually declines it (dense RS is not XOR-sparse)
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"})
    from ceph_tpu.ops.xla_ops import matrix_to_static
    ms = matrix_to_static(ec.matrix)
    assert probe_schedule(ms, 8) is not None
    t = BestConfigTable()
    t.set(tuning_key("m:" + matrix_digest(ms), "matrix-engine", "*",
                     "bytes", 1, 0), {"engine": "xor"}, mode="timed")
    ttable.install_table(t)
    assert select_matrix_engine((2, 4, 4096), ms, 8, engine="xla",
                                mesh=0) == "xor"
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 256, (2, 4, 4096), dtype=np.uint8))
    out = np.asarray(_run_matrix_bytes(x, ms, 8, "xor"))
    ttable.install_table(None)
    ref_eng = select_matrix_engine((2, 4, 4096), ms, 8, engine="xla",
                                   mesh=0)
    ref = np.asarray(_run_matrix_bytes(x, ms, 8, ref_eng))
    assert np.array_equal(out, ref)
    # (whether the cutover prefers this matrix is the model's call —
    # the pin must dispatch either way, which is what ran above)
    preferred_schedule(ms, 8)


def test_tuned_ladder_seam():
    from ceph_tpu.serve.batcher import LADDER, ContinuousBatcher
    t = _tuned_global_table(
        {("serve-ladder", "*", "*"): {"ladder": [1, 2, 8, 32]}})
    ttable.install_table(t)
    b = ContinuousBatcher(executor="host")
    assert b.ladder == (1, 2, 8, 32)
    # an explicit ladder (scenario specs, tests) always wins
    b2 = ContinuousBatcher(executor="host", ladder=(1, 4))
    assert b2.ladder == (1, 4)
    # invalid tuned ladders fall back
    bad = _tuned_global_table(
        {("serve-ladder", "*", "*"): {"ladder": [4, 1, 1]}})
    ttable.install_table(bad)
    assert ContinuousBatcher(executor="host").ladder == LADDER


def test_tuned_fanout_seam():
    import jax

    from ceph_tpu.parallel import plane as pl
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device virtual mesh")
    t = BestConfigTable()
    t.set(tuning_key("*", "mesh-fanout", "mesh", "*",
                     current_env()["device_count"], 0),
          {"n_devices": 2}, mode="analytic")
    ttable.install_table(t)
    prev = pl.set_data_plane(None)
    try:
        auto = pl.activate(None)
        assert auto is not None and auto.n_devices == 2
        # an explicit width always wins over the tuned default
        explicit = pl.activate(4)
        assert explicit is not None and explicit.n_devices == 4
    finally:
        pl.set_data_plane(prev)


# ----------------------------------------------------------------------
# fallback byte-identity: tuned vs default outputs identical across
# all five plugin families, byte + packed layouts (ISSUE 14 satellite)

FAMILIES5 = ("jerasure", "isa", "shec", "lrc", "clay")


def _family_outputs(family, seed=11):
    """Every device surface's output on seeded random input — the
    surfaces are linear maps, so byte-identity on arbitrary input IS
    byte-identity (no need for a structurally valid codeword)."""
    from ceph_tpu.analysis.entrypoints import REPRESENTATIVE_PROFILES
    plugin, profile = REPRESENTATIVE_PROFILES[family]
    ec = ErasureCodePluginRegistry.instance().factory(
        plugin, dict(profile))
    rng = np.random.default_rng(seed)
    k = ec.get_data_chunk_count()
    n = ec.get_chunk_count()
    erased = (1,)
    available = tuple(i for i in range(n) if i != 1)
    data = rng.integers(0, 256, (2, k, 4096), dtype=np.uint8)
    surv = rng.integers(0, 256, (2, len(available), 4096),
                        dtype=np.uint8)
    out = {"enc": np.asarray(ec.encode_chunks_jax(data)),
           "dec": np.asarray(
               ec.decode_chunks_jax(surv, available, erased))}
    if hasattr(type(ec), "encode_chunks_packed_jax"):
        pdata = np.ascontiguousarray(data).view(np.uint32).reshape(
            2, k, 4096 // 512, 128)
        out["enc_packed"] = np.asarray(
            ec.encode_chunks_packed_jax(pdata))
    if hasattr(type(ec), "decode_chunks_packed_jax"):
        psurv = np.ascontiguousarray(surv).view(np.uint32).reshape(
            2, len(available), 4096 // 512, 128)
        out["dec_packed"] = np.asarray(
            ec.decode_chunks_packed_jax(psurv, available, erased))
    return out


@pytest.mark.parametrize("family", FAMILIES5)
def test_tuned_vs_default_byte_identity(family):
    """The acceptance pin: a tuned table moves computation between
    tiers, it NEVER changes output bytes — for every plugin family,
    bytes and packed layouts, encode and decode."""
    default_out = _family_outputs(family)
    rep = tsweep.analytic_sweep(seed=3)
    assert len(rep.table) > 0
    ttable.install_table(rep.table)
    tuned_out = _family_outputs(family)
    ttable.install_table(None)
    again = _family_outputs(family)
    assert set(tuned_out) == set(default_out)
    for name in sorted(default_out):
        assert np.array_equal(tuned_out[name], default_out[name]), \
            f"{family}.{name}: tuned output diverged from default"
        assert np.array_equal(again[name], default_out[name]), \
            f"{family}.{name}: uninstall did not restore defaults"


# ----------------------------------------------------------------------
# zero warm recompiles under a tuned table (ISSUE 14 satellite)


def test_zero_warm_recompiles_with_tuned_table():
    """Tuned configs are consulted at program-BUILD time: after a
    warmup under an installed table, repeat dispatches compile
    nothing — pinned with the armed recompile budget AND the jax
    compile counter, exactly like the serving acceptance gate."""
    import jax

    from ceph_tpu.analysis.jaxpr_audit import _CompileCounter
    from ceph_tpu.codes.engine import (fused_repair_call,
                                       global_pattern_cache,
                                       serve_dispatch_call)
    rep = tsweep.analytic_sweep(seed=3)
    ttable.install_table(rep.table)
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"})
    n = ec.get_chunk_count()
    erased = (1,)
    available = tuple(i for i in range(n) if i != 1)
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, (4, ec.get_data_chunk_count(), 4096),
                        dtype=np.uint8)
    surv = rng.integers(0, 256, (4, len(available), 4096),
                        dtype=np.uint8)
    # warm: build the tuned programs (compiles happen HERE, once)
    enc = serve_dispatch_call(ec, "encode")
    rep_call = fused_repair_call(ec, available, erased)
    jax.block_until_ready(enc(data))
    jax.block_until_ready(rep_call(surv))
    cache = global_pattern_cache()
    prev_budget = cache.recompile_budget
    cache.recompile_budget = cache.builds     # arm: any build raises
    try:
        with _CompileCounter() as counter:
            for _ in range(3):
                out1 = enc(data)
                out2 = rep_call(surv)
            jax.block_until_ready((out1, out2))
    finally:
        cache.recompile_budget = prev_budget
    assert counter.count == 0, \
        f"warm tuned path compiled {counter.count} program(s)"


# ----------------------------------------------------------------------
# analytic sweep: determinism + the audit entry


def test_analytic_sweep_deterministic():
    kwargs = dict(seed=17, platform="cpu", device_count=1,
                  chunk=2048, batch=4, families=("jerasure", "shec"))
    d1 = tsweep.analytic_sweep(**kwargs).to_dict()
    d2 = tsweep.analytic_sweep(**kwargs).to_dict()
    assert json.dumps(d1, sort_keys=True) == \
        json.dumps(d2, sort_keys=True)
    # a different seed may legitimately differ (the ladder model's
    # occupancy stream is seeded) but must still be valid
    d3 = tsweep.analytic_sweep(**{**kwargs, "seed": 18}).to_dict()
    assert d3["table_valid"]


def test_sweep_rows_have_before_after_utilization():
    rep = tsweep.analytic_sweep(seed=5)
    assert rep.rows
    matrix_rows = [r for r in rep.rows if r["kind"] == "matrix-engine"]
    assert matrix_rows, "no per-program before/after rows"
    for r in matrix_rows:
        assert r["before"].get("utilization_pct") is not None
        assert r["after"].get("utilization_pct") is not None
    # the acceptance criterion: >= 1 hot program from the audit
    # registry's families shows a (modeled, device-free)
    # improvement
    assert any((r.get("improvement_pct") or 0) > 0 for r in rep.rows)
    names = {r["name"].split(".")[0] for r in matrix_rows}
    from ceph_tpu.analysis.entrypoints import REPRESENTATIVE_PROFILES
    assert names & set(REPRESENTATIVE_PROFILES)


def test_tune_sweep_audit_entry_registered_and_clean():
    from ceph_tpu.analysis.entrypoints import registry
    from ceph_tpu.analysis.jaxpr_audit import (audit_entry_point,
                                               run_sentinel)
    by_name = {e.name: e for e in registry()}
    ep = by_name["tune.sweep"]
    assert ep.kind == "host" and ep.trace_budget == 0
    audit = audit_entry_point(ep)
    assert not audit.findings, [f.render() for f in audit.findings]
    sent = run_sentinel(ep)
    assert not sent.findings, [f.render() for f in sent.findings]
    assert sent.cold_compiles == 0 and sent.warm_compiles == 0


def test_jit_entries_stay_clean_with_tuned_table():
    """The satellite re-verification: representative jit-tier entries
    audit 0-findings (and warm==0) WITH a tuned table installed — a
    tuned config can reroute a program, it cannot make it drift off
    its primitive allowlist or churn the trace cache."""
    from ceph_tpu.analysis.entrypoints import registry
    from ceph_tpu.analysis.jaxpr_audit import (audit_entry_point,
                                               run_sentinel)
    rep = tsweep.analytic_sweep(seed=3)
    ttable.install_table(rep.table)
    by_name = {e.name: e for e in registry()}
    for name in ("ops.apply_matrix_best", "ops.apply_matrix_packed_best",
                 "engine.fused_repair_call", "serve.dispatch"):
        audit = audit_entry_point(by_name[name])
        assert not audit.findings, \
            (name, [f.render() for f in audit.findings])
        sent = run_sentinel(by_name[name])
        assert not sent.findings, \
            (name, [f.render() for f in sent.findings])
        assert sent.warm_compiles == 0


@pytest.mark.slow
def test_full_registry_clean_with_tuned_table():
    """The full satellite: EVERY jit-tier entry stays 0-findings with
    a tuned table installed (the fast subset above runs in tier-1)."""
    from ceph_tpu.analysis.entrypoints import registry
    from ceph_tpu.analysis.jaxpr_audit import audit_entry_point
    rep = tsweep.analytic_sweep(seed=3)
    ttable.install_table(rep.table)
    for ep in registry():
        if ep.kind != "jit":
            continue
        audit = audit_entry_point(ep)
        assert not audit.findings, \
            (ep.name, [f.render() for f in audit.findings])


# ----------------------------------------------------------------------
# timed sweep (CPU backend is a real backend — the mechanics hold)


def test_timed_sweep_pins_and_byte_identity():
    rep = tsweep.timed_sweep(size=1 << 14, batch=4, repeats=2, seed=9)
    assert rep.mode == "timed"
    assert rep.rows, "timed sweep produced no rows"
    for r in rep.rows:
        assert r["before"].get("p50_ms") is not None
    # every persisted entry records both configs and both scores
    for entry in rep.table.entries.values():
        assert entry["mode"] == "timed"
        assert entry["score"] is not None
        assert entry["baseline_score"] is not None


# ----------------------------------------------------------------------
# bench integration (metric_version 11)


def test_bench_autotune_workload_host_row():
    from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench
    bench = ErasureCodeBench()
    bench.setup(["--workload", "autotune", "--device", "host",
                 "--seed", "42"])
    res = bench.run()
    assert res["workload"] == "autotune"
    assert res["mode"] == "analytic"
    assert res["config_source"] == "default"
    assert res["tune_key_hash"] is None
    assert res["n_tuned"] == len(res["tuned_keys"]) > 0
    assert isinstance(res["utilization_pct"], (int, float))
    assert res["rows"] and all("before" in r and "after" in r
                               for r in res["rows"])
    assert res["verified"] is True
    assert res["gbps"] > 0


def test_bench_rows_carry_config_source(tmp_path):
    """Every workload row is config-provenanced: default with no
    table, tuned + content hash under --tune-table."""
    from ceph_tpu.bench.erasure_code_benchmark import ErasureCodeBench
    bench = ErasureCodeBench()
    bench.setup(["--workload", "encode", "--device", "host",
                 "--size", "4096", "--batch", "2",
                 "--plugin", "jerasure",
                 "--parameter", "technique=reed_sol_van",
                 "--parameter", "k=2", "--parameter", "m=1"])
    res = bench.run()
    assert res["config_source"] == "default"
    assert res["tune_key_hash"] is None
    rep = tsweep.analytic_sweep(seed=3)
    path = str(tmp_path / "t.json")
    rep.table.save(path)
    bench2 = ErasureCodeBench()
    bench2.setup(["--workload", "encode", "--device", "host",
                  "--size", "4096", "--batch", "2",
                  "--plugin", "jerasure",
                  "--parameter", "technique=reed_sol_van",
                  "--parameter", "k=2", "--parameter", "m=1",
                  "--tune-table", path])
    res2 = bench2.run()
    assert res2["config_source"] == "tuned"
    assert res2["tune_key_hash"] == rep.table.content_hash()


def test_bench_py_autotune_row_plumbing(monkeypatch):
    import bench
    assert ("autotune_rows" in [  # declared next to its siblings
        "autotune_rows"]) and dict(bench.AUTOTUNE_ROWS)
    row = bench._row_result({"gbps": 1.0, "config_source": "tuned",
                             "tune_key_hash": "abc123"})
    assert row["config_source"] == "tuned"
    assert row["tune_key_hash"] == "abc123"
    calls = []

    def fake_run(argv):
        calls.append(argv)
        return {"gbps": 2.0, "mode": "analytic", "n_tuned": 3,
                "tuned_keys": ["a"], "utilization_pct": 42.0,
                "improvement_pct": 7.0, "improved_rows": 1,
                "rows": [], "verified": True,
                "config_source": "default", "tune_key_hash": None}

    monkeypatch.setattr(bench, "_run", fake_run)
    rows = bench._section("autotune", bench.AUTOTUNE_ROWS,
                          bench.AUTOTUNE_ROW_FIELDS)
    assert rows["rs_k8_m3_autotune"]["utilization_pct"] == 42.0
    assert rows["rs_k8_m3_autotune"]["mode"] == "analytic"
    assert calls[0] == dict(bench.AUTOTUNE_ROWS)["rs_k8_m3_autotune"]


# ----------------------------------------------------------------------
# bench_diff: the autotune category (red fixture)


def test_bench_diff_autotune_red(tmp_path):
    """A tuned config whose utilization later collapses trips the
    sentinel under its own category while the headline holds."""
    import os
    script = os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": {
            "metric": "m", "value": 100.0, "git_sha": "aaa",
            "timestamp": "2026-01-01T00:00:00+00:00",
            "autotune_rows": {"rs_k8_m3_autotune": {
                "gbps": 0.01, "utilization_pct": 80.0}}}}))
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(
        {"metric": "m", "value": 100.0, "git_sha": "bbb",
         "timestamp": "2026-02-01T00:00:00+00:00",
         "autotune_rows": {"rs_k8_m3_autotune": {
             "gbps": 0.01, "utilization_pct": 20.0}}}))
    r = subprocess.run([sys.executable, script, "--repo",
                        str(tmp_path)],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 4, r.stdout
    assert "autotune:rs_k8_m3_autotune" in r.stderr


def test_bench_diff_autotune_green_within_floor(tmp_path):
    import os
    script = os.path.join(REPO_ROOT, "tools", "bench_diff.py")
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "", "parsed": {
            "metric": "m", "value": 100.0, "git_sha": "aaa",
            "timestamp": "2026-01-01T00:00:00+00:00",
            "autotune_rows": {"rs_k8_m3_autotune": {
                "gbps": 0.01, "utilization_pct": 80.0}}}}))
    (tmp_path / "BENCH_CURRENT.json").write_text(json.dumps(
        {"metric": "m", "value": 100.0, "git_sha": "bbb",
         "timestamp": "2026-02-01T00:00:00+00:00",
         "autotune_rows": {"rs_k8_m3_autotune": {
             "gbps": 0.01, "utilization_pct": 60.0}}}))
    r = subprocess.run([sys.executable, script, "--repo",
                        str(tmp_path)],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr


# ----------------------------------------------------------------------
# the CLI (the test_full.sh smoke gate's exact invocation)


def test_autotune_cli_analytic(tmp_path):
    import os
    script = os.path.join(REPO_ROOT, "tools", "autotune.py")
    out = str(tmp_path / "table.json")
    r = subprocess.run(
        [sys.executable, script, "--analytic", "--out", out,
         "--validate"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    table = BestConfigTable.load(out)
    assert validate_table(table.to_dict()) == []
    assert len(table) > 0
    assert "before/after" in r.stdout
