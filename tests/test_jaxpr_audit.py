"""tpu-audit (ceph_tpu/analysis/jaxpr_audit) — trace-tier gate.

Three layers, mirroring test_tpu_lint.py's structure one tier down:

- every audit-* rule has a deliberately-bad traced function proving it
  fires (float leak, host callback, baked transfer, weak-typed scalar,
  off-allowlist primitive), plus sentinel batteries (warm retrace,
  budget breach, silent numpy-tier fall-through, impure host tier);
- suppressions share the AST tier's pragma syntax: a
  ``# tpu-lint: disable=audit-* -- reason`` near the traced def
  suppresses, and stale audit pragmas are flagged;
- the repo gate: the FULL registry (every plugin family, engine,
  crush bulk, scrub) audits clean with the recompile sentinel inside
  its declared budgets, and the registry-completeness check fails
  when a public device surface goes unregistered.

Runs on CPU (JAX_PLATFORMS=cpu in tier-1): tracing is
backend-independent; the same jaxprs lower on TPU.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "lint_fixtures")
sys.path.insert(0, ROOT)

from ceph_tpu.analysis.entrypoints import (  # noqa: E402
    Built,
    EntryPoint,
    registry,
    registry_gaps,
)
from ceph_tpu.analysis.jaxpr_audit import (  # noqa: E402
    AUDIT_RULE_IDS,
    SENTINEL_RULE,
    TraceReport,
    audit_entry_point,
    audit_registry,
    collect_primitives,
    run_sentinel,
    stale_trace_pragmas,
)

BASE_ALLOW = frozenset({
    "jit", "convert_element_type", "add", "xor", "and", "mul",
    "reshape", "broadcast_in_dim", "slice", "concatenate", "squeeze",
    "shift_left", "shift_right_logical", "bitcast_convert_type",
})


def _entry(fn, args, name="synthetic.fn", kind="jit", allow=BASE_ALLOW,
           float_ok=frozenset(), trace_budget=8, anchor=None):
    return EntryPoint(
        name=name, family="ops", kind=kind,
        build=lambda: Built(fn, args, anchor if anchor is not None
                            else fn),
        allow=allow, float_ok=float_ok, trace_budget=trace_budget)


def _rules(findings):
    return {f.rule for f in findings}


# ----------------------------------------------------------------------
# red battery: each trace rule fires on a deliberately-bad function

def test_float_lane_fires_on_float_leak():
    def leak(x):
        return x.astype(jnp.float32).astype(jnp.uint8)

    audit = audit_entry_point(_entry(leak, (np.zeros((4, 8), np.uint8),)))
    assert "audit-float-lane" in _rules(audit.findings)


def test_float_lane_respects_float_ok_whitelist():
    def leak(x):
        return x.astype(jnp.float32).astype(jnp.uint8)

    audit = audit_entry_point(_entry(
        leak, (np.zeros((4, 8), np.uint8),),
        float_ok=frozenset({"convert_element_type"})))
    assert "audit-float-lane" not in _rules(audit.findings)


def test_callback_fires_on_pure_callback():
    def cb(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape,
                                                          x.dtype), x)

    audit = audit_entry_point(_entry(cb, (np.zeros((4,), np.uint8),)))
    assert "audit-callback" in _rules(audit.findings)


def test_callback_fires_on_debug_callback():
    def cb(x):
        jax.debug.print("x sum {s}", s=x.sum())
        return x

    audit = audit_entry_point(_entry(cb, (np.zeros((4,), np.uint8),)))
    assert "audit-callback" in _rules(audit.findings)


def test_transfer_fires_on_baked_device_put():
    def xfer(x):
        idx = jax.device_put(np.array([1, 0]))
        return x[idx]

    audit = audit_entry_point(_entry(xfer, (np.zeros((4, 8), np.uint8),)))
    assert "audit-transfer" in _rules(audit.findings)


def test_transfer_fires_on_np_fancy_indexing():
    # the exact shape the shec decode surfaces shipped with: numpy
    # fancy indexing inside a traced fn bakes a device_put of the
    # index constant + a dynamic gather into the program
    def fancy(x):
        return x[:, np.array([2, 0, 1])]

    audit = audit_entry_point(_entry(fancy, (np.zeros((4, 8), np.uint8),)))
    assert "audit-transfer" in _rules(audit.findings)


def test_weak_type_fires_on_python_scalar_argument():
    def scale(x, s):
        return x * s

    audit = audit_entry_point(_entry(
        scale, (np.zeros((4,), np.int32), 3)))
    assert "audit-weak-type" in _rules(audit.findings)


def test_weak_type_fires_on_inner_jit_boundary():
    @jax.jit
    def inner(x, s):
        return x * s

    def outer(x, s):
        return inner(x, s)

    audit = audit_entry_point(_entry(
        outer, (np.zeros((4,), np.int32), 3)))
    msgs = [f.message for f in audit.findings
            if f.rule == "audit-weak-type"]
    assert any("jit boundary" in m for m in msgs), msgs


def test_allowlist_fires_on_primitive_drift():
    def drift(x):
        return jnp.sort(x)

    audit = audit_entry_point(_entry(drift, (np.zeros((8,), np.uint8),)))
    hits = [f for f in audit.findings
            if f.rule == "audit-primitive-allowlist"]
    assert hits and any("'sort'" in f.message for f in hits)


def test_allowlist_none_skips_rule():
    def drift(x):
        return jnp.sort(x)

    audit = audit_entry_point(_entry(drift, (np.zeros((8,), np.uint8),),
                                     allow=None))
    assert "audit-primitive-allowlist" not in _rules(audit.findings)


def test_clean_function_audits_clean():
    def ok(x):
        return (x ^ (x << 1)) & 0xFF

    audit = audit_entry_point(_entry(
        ok, (np.zeros((4, 8), np.uint8),),
        allow=BASE_ALLOW | frozenset({"rem"})))
    assert audit.ok, [f.render() for f in audit.findings]
    assert audit.n_eqns > 0 and audit.primitives


def test_rules_recurse_into_scan_bodies():
    def scanned(x):
        def body(c, row):
            return c, row.astype(jnp.float32).astype(jnp.uint8)

        return jax.lax.scan(body, jnp.uint8(0), x)[1]

    audit = audit_entry_point(_entry(
        scanned, (np.zeros((4, 8), np.uint8),),
        allow=BASE_ALLOW | frozenset({"scan"})))
    assert "audit-float-lane" in _rules(audit.findings)


def test_build_error_is_a_finding_and_unsuppressible():
    def broken_build():
        raise RuntimeError("no such workload")

    ep = EntryPoint(name="synthetic.broken", family="ops", kind="jit",
                    build=broken_build, allow=None)
    rep = audit_registry([ep], sentinel=False, completeness=False)
    assert not rep.ok
    assert _rules(rep.findings) == {"audit-error"}


# ----------------------------------------------------------------------
# recompile sentinel

def test_sentinel_clean_on_stable_jit():
    @jax.jit
    def stable(x):
        return x ^ 0x5A

    ep = _entry(stable, (jnp.zeros((8,), jnp.uint8),), trace_budget=4)
    audit = run_sentinel(ep)
    assert audit.ok, [f.render() for f in audit.findings]
    assert audit.warm_compiles == 0


def test_sentinel_flags_warm_retrace():
    def churn(x):
        # a fresh jit wrapper per call: the trace cache can never hit
        return jax.jit(lambda y: y ^ 1)(x)

    ep = _entry(churn, (jnp.zeros((8,), jnp.uint8),), trace_budget=64)
    audit = run_sentinel(ep)
    msgs = [f.message for f in audit.findings
            if f.rule == SENTINEL_RULE]
    assert any("warm repeat" in m for m in msgs), msgs


def test_sentinel_flags_budget_breach():
    @jax.jit
    def fresh(x):
        return x + jnp.uint8(7)

    ep = _entry(fresh, (jnp.full((3, 5), 1, jnp.uint8),),
                trace_budget=0)
    audit = run_sentinel(ep)
    msgs = [f.message for f in audit.findings
            if f.rule == SENTINEL_RULE]
    assert any("declared budget" in m for m in msgs), msgs


def test_sentinel_flags_silent_numpy_tier():
    def hostish(x):
        return np.asarray(x) ^ 1   # never touches jax

    ep = _entry(hostish, (np.zeros((8,), np.uint8),), trace_budget=4)
    audit = run_sentinel(ep)
    msgs = [f.message for f in audit.findings
            if f.rule == SENTINEL_RULE]
    assert any("numpy tier" in m for m in msgs), msgs


def test_sentinel_host_tier_clean_and_impure():
    def pure_host(x):
        return np.bitwise_xor.reduce(x, axis=-1)

    ok = run_sentinel(_entry(pure_host, (np.zeros((4, 8), np.uint8),),
                             kind="host", trace_budget=0))
    assert ok.ok, [f.render() for f in ok.findings]

    def sneaky_host(x):
        return np.asarray(jnp.asarray(x) ^ 1)

    bad = run_sentinel(_entry(sneaky_host,
                              (np.full((4, 8), 3, np.uint8),),
                              kind="host", trace_budget=0))
    msgs = [f.message for f in bad.findings if f.rule == SENTINEL_RULE]
    assert any("host-tier" in m for m in msgs), msgs


# ----------------------------------------------------------------------
# suppression sharing + stale audit pragmas

def _load_fixture(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(FIXTURES, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_suppression_shares_pragma_syntax():
    mod = _load_fixture("trace_float_suppressed")
    audit = audit_entry_point(_entry(
        mod.float_leak, (np.zeros((4, 8), np.uint8),), allow=None,
        anchor=mod.float_leak))
    assert "audit-float-lane" not in _rules(audit.findings)
    sup = [f for f in audit.suppressed if f.rule == "audit-float-lane"]
    assert sup and all(f.suppress_reason for f in sup)


def test_stale_trace_pragma_flagged(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("# tpu-lint: disable=audit-callback -- long gone\n"
                 "def fn(x):\n"
                 "    return x\n")
    report = TraceReport(entries=[])
    stale = stale_trace_pragmas([str(tmp_path)], report)
    assert len(stale) == 1
    assert "audit-callback" in stale[0].message
    assert stale[0].rule == "stale-suppression"


def test_used_trace_pragma_not_stale():
    mod = _load_fixture("trace_float_suppressed")
    ep = _entry(mod.float_leak, (np.zeros((4, 8), np.uint8),),
                allow=None, anchor=mod.float_leak)
    report = audit_registry([ep], sentinel=False, completeness=False)
    stale = stale_trace_pragmas(
        [os.path.join(FIXTURES, "trace_float_suppressed.py")], report)
    assert stale == []


# ----------------------------------------------------------------------
# the repo gate: full registry, clean, within budgets

def test_full_registry_audits_clean():
    rep = audit_registry()
    msgs = "\n".join(f.render() for f in rep.findings)
    assert rep.ok, f"unsuppressed tpu-audit findings:\n{msgs}\n" \
                   f"gaps: {rep.gaps}"
    for e in rep.entries:
        assert e.warm_compiles == 0, \
            f"{e.name} retraced on a warm repeat"
        # suppressed trace findings must carry a reason, like the AST
        # tier's gate
        for f in e.suppressed:
            assert f.suppress_reason, f.render()


def test_registry_covers_required_surfaces():
    entries = registry()
    assert len(entries) >= 12
    fams = {e.family for e in entries}
    assert {"jerasure", "isa", "shec", "lrc", "clay",
            "engine", "ops", "crush", "scrub"} <= fams
    names = {e.name for e in entries}
    assert "engine.fused_repair_call" in names
    assert "crush.bulk_rule" in names
    assert "scrub.ceph_crc32c_batch" in names
    assert "ops.apply_matrix_mxu" in names
    # every declared audit rule is exercised by the red battery above
    assert set(AUDIT_RULE_IDS) == {
        "audit-float-lane", "audit-callback", "audit-transfer",
        "audit-weak-type", "audit-primitive-allowlist"}


def test_registry_completeness_catches_missing_surface(monkeypatch):
    import ceph_tpu.analysis.entrypoints as eps

    full = list(registry())
    pruned = [e for e in full if e.name != "clay.decode_chunks_jax"]
    monkeypatch.setattr(eps, "registry", lambda: tuple(pruned))
    gaps = eps.registry_gaps()
    assert "clay.decode_chunks_jax" in gaps


def test_registry_gaps_clean_on_real_registry():
    assert registry_gaps() == []


def test_mxu_float_whitelist_is_load_bearing():
    """The MXU entry's floats are DECLARED (float_ok), not invisible:
    stripping the declaration must turn its audit red — proving
    audit-float-lane still guards every primitive around the one
    sanctioned bit-plane region."""
    import dataclasses

    ep = {e.name: e for e in registry()}["ops.apply_matrix_mxu"]
    clean = audit_entry_point(ep)
    assert clean.ok, [f.render() for f in clean.findings]
    stripped = dataclasses.replace(ep, float_ok=frozenset())
    audit = audit_entry_point(stripped)
    assert "audit-float-lane" in _rules(audit.findings)


# ----------------------------------------------------------------------
# regression: the genuine findings the auditor surfaced

@pytest.mark.parametrize("surface", ["decode_chunks_jax",
                                     "decode_chunks_packed_jax"])
def test_shec_decode_traces_without_gather_or_transfer(surface):
    """shec's decode surfaces used np fancy indexing on the traced
    stack, baking a device_put of the index constant plus a dynamic
    gather (with clamp/select plumbing) into every decode program;
    take_static lowers the same static selection to slices."""
    from ceph_tpu.analysis.entrypoints import representative_instance

    ec = representative_instance("shec")
    n = ec.get_chunk_count()
    available = tuple(i for i in range(n) if i != 1)
    if surface == "decode_chunks_jax":
        fn = lambda c: ec.decode_chunks_jax(c, available, (1,))  # noqa: E731
        arg = np.zeros((2, len(available), 1024), np.uint8)
    else:
        fn = lambda w: ec.decode_chunks_packed_jax(w, available, (1,))  # noqa: E731
        arg = np.zeros((2, len(available), 2, 128), np.uint32)
    prims = collect_primitives(jax.make_jaxpr(fn)(arg))
    assert "device_put" not in prims
    assert "gather" not in prims


def test_take_static_matches_fancy_indexing():
    from ceph_tpu.ops.xla_ops import take_static

    rng = np.random.default_rng(7)
    x = rng.integers(0, 256, (3, 6, 32), dtype=np.uint8)
    for idx in ([2, 0, 4], [1, 2, 3], [5], [0, 0, 2]):
        got = np.asarray(take_static(jnp.asarray(x), idx, axis=1))
        np.testing.assert_array_equal(got, x[:, np.array(idx)])
    got = np.asarray(take_static(jnp.asarray(x), [], axis=1))
    assert got.shape == (3, 0, 32)


def test_shec_decode_byte_identity_after_take_static():
    """The static-slice rewrite must be byte-identical to the numpy
    ground truth (the actual repair path contract)."""
    from ceph_tpu.analysis.entrypoints import representative_instance

    ec = representative_instance("shec")
    rng = np.random.default_rng(11)
    k, n = ec.get_data_chunk_count(), ec.get_chunk_count()
    data = rng.integers(0, 256, (2, k, 1024), dtype=np.uint8)
    parity = np.asarray(ec.encode_chunks_batch(data))
    stack = np.concatenate([data, parity], axis=1)
    available = tuple(i for i in range(n) if i != 1)
    erased = (1,)
    got = np.asarray(ec.decode_chunks_jax(
        stack[:, list(available)], available, erased))
    ref = ec.decode_chunks_batch(stack[:, list(available)], available,
                                 erased)
    np.testing.assert_array_equal(got, np.asarray(ref))


# ----------------------------------------------------------------------
# registry gaps are first-class findings (ISSUE 16 satellite): a gap
# with ZERO per-entry findings must still fail the run and render

def test_registry_gap_alone_fails_and_renders():
    report = TraceReport(entries=[], gaps=["clay.decode_chunks_jax"])
    assert not report.ok                      # the non-zero-exit driver
    assert report.findings == []              # no AST/trace findings...
    [gf] = report.gap_findings                # ...the gap IS the finding
    assert gf.rule == "audit-registry-gap"
    assert "clay.decode_chunks_jax" in gf.message
    assert "entrypoints.py" in gf.message
    # grep-able path:line:col: [rule] shape like every other finding
    assert "[audit-registry-gap]" in gf.render()


def test_render_trace_carries_gap_findings():
    import json as _json

    from ceph_tpu.analysis.report import (render_trace_human,
                                          render_trace_json)

    report = TraceReport(entries=[], gaps=["ops.missing_surface"])
    human = render_trace_human(report)
    assert "audit-registry-gap" in human
    assert "ops.missing_surface" in human
    doc = _json.loads(render_trace_json(report))
    assert doc["ok"] is False
    assert doc["tier"] == "trace"
    assert doc["lint_schema_version"] == 2
    assert any(g["rule"] == "audit-registry-gap"
               for g in doc["gap_findings"])
