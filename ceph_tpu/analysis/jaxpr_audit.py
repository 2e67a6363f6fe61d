"""tpu-audit — trace-tier analysis: walk the jaxpr XLA is actually
asked to run for every registered entry point.

The AST tier (rules.py) sees code shapes; the runtime tier
(CEPH_TPU_VERIFY) sees bytes.  Neither sees what a helper call chain
*traces to*: a float ``convert_element_type`` introduced three modules
away, a ``pure_callback`` smuggled into a hot path, a weak-typed scalar
poisoning a jit cache key.  This tier traces each registered entry
point (analysis/entrypoints.py) to a ClosedJaxpr and walks every
equation, recursing into jit/scan/while/cond/pallas_call sub-jaxprs:

- ``audit-float-lane``    — no inexact dtype may appear in a GF-lane
  program outside the entry's whitelisted primitives (the MXU
  bit-plane region is the only sanctioned float user; PARITY.md).
- ``audit-callback``      — no ``io_callback`` / ``pure_callback`` /
  ``debug_callback`` in a traced hot path (each is a host round-trip
  per dispatch).
- ``audit-transfer``      — no ``device_put`` and no host array
  constant inside a traced region
  (a transfer baked into the program defeats the batch-first design).
- ``audit-weak-type``     — no weak-typed avals entering the program
  or crossing an inner jit boundary (Python scalars that fork the jit
  cache key per call site and force recompiles).
- ``audit-primitive-allowlist`` — the traced primitive set must stay
  inside the entry's declared family set; drift fails loudly.

A companion *recompile sentinel* (``run_sentinel``) executes each
entry's representative workload twice under compile-count
instrumentation (jax.monitoring): the cold run must stay within the
entry's declared ``trace_budget``, the warm repeat must compile
NOTHING, jit-tier entries must actually return device arrays (an entry
silently falling to the numpy tier is a finding, not a pass), and
host-tier entries must never dispatch through jax at all.

Suppressions share the AST tier's pragma syntax (analysis/suppress.py):
findings anchor to the traced function's def in its source file, so
``# tpu-lint: disable=audit-float-lane -- reason`` near that def
suppresses exactly like an AST finding.  ``audit-error`` (an entry that
fails to build or trace) is never suppressible.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from .entrypoints import Built, EntryPoint, registry, registry_gaps
from .rules import Finding
from .suppress import PragmaInfo, collect_pragmas
from ..utils.locks import make_lock

AUDIT_RULE_IDS = (
    "audit-float-lane",
    "audit-callback",
    "audit-transfer",
    "audit-weak-type",
    "audit-primitive-allowlist",
)
SENTINEL_RULE = "audit-recompile"
ERROR_RULE = "audit-error"

CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback", "debug_print",
                            "debug_callback"})
TRANSFER_PRIMS = frozenset({"device_put"})

# duration events jax.monitoring emits once per backend compile; the
# sentinel counts them (one listener, registered lazily, process-wide)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ----------------------------------------------------------------------
# jaxpr walking

def _jaxpr_types():
    from jax.extend import core

    return core.ClosedJaxpr, core.Jaxpr, core.Literal


def _sub_jaxprs(value) -> Iterator[object]:
    """Yield every Jaxpr reachable from an eqn param value (jit's
    ClosedJaxpr, scan/while bodies, cond branch tuples, pallas_call's
    raw Jaxpr)."""
    ClosedJaxpr, Jaxpr, _ = _jaxpr_types()
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterator[object]:
    """Every equation of ``jaxpr``, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def collect_primitives(closed) -> Dict[str, int]:
    """primitive name -> count over the whole (recursive) program."""
    counts: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts


# ----------------------------------------------------------------------
# per-entry audit report

@dataclasses.dataclass
class EntryAudit:
    name: str
    family: str
    kind: str
    findings: List[Finding]
    suppressed: List[Finding]
    primitives: Dict[str, int] = dataclasses.field(default_factory=dict)
    n_eqns: int = 0
    cold_compiles: Optional[int] = None
    warm_compiles: Optional[int] = None

    @property
    def ok(self) -> bool:
        return not self.findings


@dataclasses.dataclass
class TraceReport:
    entries: List[EntryAudit]
    gaps: List[str] = dataclasses.field(default_factory=list)
    # per-source-file pragma state from this run (suppression `used`
    # flags included) — input to stale_trace_pragmas
    pragmas: Dict[str, PragmaInfo] = dataclasses.field(
        default_factory=dict)
    stale: List[Finding] = dataclasses.field(default_factory=list)

    @property
    def findings(self) -> List[Finding]:
        return [f for e in self.entries for f in e.findings]

    @property
    def suppressed(self) -> List[Finding]:
        return [f for e in self.entries for f in e.suppressed]

    @property
    def gap_findings(self) -> List[Finding]:
        """Registry gaps as first-class findings: a gap alone (zero
        per-entry findings) must still fail the run and render in the
        same grep-able ``path:line:col: [rule]`` shape as everything
        else — pinned by tests/test_tpu_lint.py."""
        return [
            Finding("audit-registry-gap", "<registry>", 0, 0, 0,
                    f"public device surface '{gap}' is not declared "
                    f"in analysis/entrypoints.py")
            for gap in self.gaps
        ]

    @property
    def ok(self) -> bool:
        return not self.findings and not self.gaps


# ----------------------------------------------------------------------
# anchoring + suppression (shared pragma syntax with the AST tier)

def _anchor_span(anchor) -> Tuple[str, int, int]:
    """(path, first line, last line) of the anchor callable's def."""
    fn = inspect.unwrap(getattr(anchor, "__func__", anchor))
    try:
        path = inspect.getsourcefile(fn) or "<unknown>"
        lines, start = inspect.getsourcelines(fn)
        return path, start, start + len(lines) - 1
    except (TypeError, OSError):
        return "<unknown>", 0, 0


def _pragmas_for(path: str,
                 cache: Optional[Dict[str, PragmaInfo]]) -> PragmaInfo:
    """Pragmas of ``path``, shared through ``cache`` so suppression
    `used` flags accumulate across entries (the stale check reads
    them after the run)."""
    key = os.path.abspath(path)
    if cache is not None and key in cache:
        return cache[key]
    try:
        with open(path, "r", encoding="utf-8") as fh:
            info = collect_pragmas(fh.read())
    except OSError:
        info = PragmaInfo(suppressions=[])
    if cache is not None:
        cache[key] = info
    return info


def _apply_suppressions(entry: EntryPoint, built: Optional[Built],
                        findings: List[Finding],
                        cache: Optional[Dict[str, PragmaInfo]] = None
                        ) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (live, suppressed) using ``# tpu-lint:``
    pragmas in the anchor's source file."""
    if built is None:
        return findings, []
    path, _, _ = _anchor_span(built.anchor)
    pragmas = _pragmas_for(path, cache)
    live: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        if f.rule == ERROR_RULE:
            live.append(f)   # broken entries cannot vouch for themselves
            continue
        sup = pragmas.suppression_for(f.rule, f.line, f.end_line)
        if sup is not None:
            f.suppressed = True
            f.suppress_reason = sup.reason
            suppressed.append(f)
        else:
            live.append(f)
    return live, suppressed


def _finding(entry: EntryPoint, built: Optional[Built], rule: str,
             message: str) -> Finding:
    if built is not None:
        path, start, end = _anchor_span(built.anchor)
    else:
        path, start, end = "<registry>", 0, 0
    return Finding(rule, path, start, 0, end,
                   f"[{entry.name}] {message}")


# ----------------------------------------------------------------------
# the five trace rules

def _check_float_lane(entry, built, closed) -> List[Finding]:
    import jax.numpy as jnp

    out: List[Finding] = []
    seen = set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in entry.float_ok:
            continue
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and jnp.issubdtype(dtype, jnp.inexact):
                key = (name, str(dtype))
                if key in seen:
                    continue
                seen.add(key)
                out.append(_finding(
                    entry, built, "audit-float-lane",
                    f"primitive '{name}' produces inexact dtype "
                    f"{dtype} in a GF-lane program (float math rounds "
                    f"parity bytes; whitelist via float_ok only for "
                    f"the MXU bit-plane region)"))
    return out


def _check_callbacks(entry, built, closed) -> List[Finding]:
    out: List[Finding] = []
    seen = set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS or name.endswith("_callback"):
            if name in seen:
                continue
            seen.add(name)
            out.append(_finding(
                entry, built, "audit-callback",
                f"host callback primitive '{name}' inside a traced hot "
                f"path (one host round-trip per dispatch)"))
    return out


def _check_transfers(entry, built, closed) -> List[Finding]:
    out: List[Finding] = []
    # a host array captured by the traced fn (jax.device_put or numpy
    # fancy indexing) is hoisted to a non-scalar program constant: an
    # upload per program build, plus the dynamic gather it feeds
    baked = [c for c in closed.consts if getattr(c, "size", 1) > 1]
    if baked:
        out.append(_finding(
            entry, built, "audit-transfer",
            f"{len(baked)} host array constant(s) baked into a traced "
            f"region (stage inputs before the jit boundary)"))
    seen = set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in TRANSFER_PRIMS:
            if name in seen:
                continue
            seen.add(name)
            out.append(_finding(
                entry, built, "audit-transfer",
                f"transfer primitive '{name}' baked into a traced "
                f"region (stage inputs before the jit boundary)"))
    return out


def _check_weak_types(entry, built, closed) -> List[Finding]:
    _, _, Literal = _jaxpr_types()
    out: List[Finding] = []
    for i, v in enumerate(closed.jaxpr.invars):
        if getattr(v.aval, "weak_type", False):
            out.append(_finding(
                entry, built, "audit-weak-type",
                f"traced argument {i} is weak-typed "
                f"({v.aval.str_short()}) — a Python scalar reaching the "
                f"trace forks the jit cache key per call site"))
    seen = set()
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "jit":
            continue
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            if getattr(v.aval, "weak_type", False):
                key = v.aval.str_short()
                if key in seen:
                    continue
                seen.add(key)
                out.append(_finding(
                    entry, built, "audit-weak-type",
                    f"weak-typed value ({key}) crosses an inner jit "
                    f"boundary (poisons that jit's cache key)"))
    return out


def _check_allowlist(entry, built, closed,
                     primitives: Dict[str, int]) -> List[Finding]:
    if entry.allow is None:
        return []
    extras = sorted(set(primitives) - set(entry.allow))
    return [
        _finding(
            entry, built, "audit-primitive-allowlist",
            f"primitive '{name}' (x{primitives[name]}) is outside the "
            f"family's declared set — either declare it (reviewed "
            f"drift) or remove the regression")
        for name in extras
    ]


# ----------------------------------------------------------------------
# compile counting (the recompile sentinel)

class _CompileCounter:
    """Counts backend compiles via jax.monitoring.  One process-wide
    listener (jax offers no unregistration); the active counter is
    swapped in under a lock."""

    _registered = False
    _lock = make_lock("analysis.jaxpr_audit._CompileCounter._lock")
    _active: Optional["_CompileCounter"] = None

    def __init__(self) -> None:
        self.count = 0

    @classmethod
    def _listener(cls, name: str, **kw) -> None:
        active = cls._active
        if active is not None and name == _COMPILE_EVENT:
            active.count += 1

    def __enter__(self) -> "_CompileCounter":
        import jax.monitoring

        with _CompileCounter._lock:
            if not _CompileCounter._registered:
                jax.monitoring.register_event_duration_secs_listener(
                    lambda name, dur, **kw:
                    _CompileCounter._listener(name, **kw))
                _CompileCounter._registered = True
            _CompileCounter._active = self
        return self

    def __exit__(self, *exc) -> None:
        with _CompileCounter._lock:
            _CompileCounter._active = None


def _block(value) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(value):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


def _has_device_leaf(value) -> bool:
    import jax

    return any(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(value))


def run_sentinel(entry: EntryPoint, built: Optional[Built] = None,
                 pragma_cache: Optional[Dict[str, PragmaInfo]] = None
                 ) -> EntryAudit:
    """Run the entry's representative workload cold + warm under
    compile counting and enforce the declared trace budget."""
    audit = EntryAudit(entry.name, entry.family, entry.kind, [], [])
    try:
        if built is None:
            built = entry.build()
        with built.scope(), _CompileCounter() as cold:
            out = built.fn(*built.args)
            _block(out)
        with built.scope(), _CompileCounter() as warm:
            out2 = built.fn(*built.args)
            _block(out2)
    except Exception as e:  # noqa: BLE001 — reported, never swallowed
        audit.findings.append(_finding(
            entry, built, ERROR_RULE,
            f"workload failed: {type(e).__name__}: {e}"))
        return audit
    audit.cold_compiles = cold.count
    audit.warm_compiles = warm.count
    findings: List[Finding] = []
    if warm.count:
        findings.append(_finding(
            entry, built, SENTINEL_RULE,
            f"warm repeat of an identical workload compiled "
            f"{warm.count} program(s) — the trace cache is not keyed "
            f"statically (pattern churn / unhashable statics)"))
    if cold.count > entry.trace_budget:
        findings.append(_finding(
            entry, built, SENTINEL_RULE,
            f"cold workload compiled {cold.count} programs "
            f"> declared budget {entry.trace_budget}"))
    if entry.kind == "jit" and not _has_device_leaf(out):
        findings.append(_finding(
            entry, built, SENTINEL_RULE,
            f"jit-tier entry returned no device array — it silently "
            f"fell to the numpy tier under audit"))
    if entry.kind == "host":
        if cold.count or warm.count:
            findings.append(_finding(
                entry, built, SENTINEL_RULE,
                f"host-tier entry dispatched {cold.count + warm.count} "
                f"jax compile(s); its contract is numpy end to end"))
        if _has_device_leaf(out):
            findings.append(_finding(
                entry, built, SENTINEL_RULE,
                f"host-tier entry returned a device array"))
    audit.findings, audit.suppressed = _apply_suppressions(
        entry, built, findings, pragma_cache)
    return audit


# ----------------------------------------------------------------------
# driving

def audit_entry_point(entry: EntryPoint, built: Optional[Built] = None,
                      pragma_cache: Optional[Dict[str, PragmaInfo]] = None
                      ) -> EntryAudit:
    """Trace one entry point and run the five trace rules (host-tier
    entries skip tracing — their whole contract is the sentinel's)."""
    import jax

    audit = EntryAudit(entry.name, entry.family, entry.kind, [], [])
    if entry.kind == "host":
        return audit
    try:
        if built is None:
            built = entry.build()
        with built.scope():
            closed = jax.make_jaxpr(built.fn)(*built.args)
    except Exception as e:  # noqa: BLE001 — reported, never swallowed
        audit.findings.append(_finding(
            entry, built, ERROR_RULE,
            f"build/trace failed: {type(e).__name__}: {e}"))
        return audit
    audit.primitives = collect_primitives(closed)
    audit.n_eqns = sum(audit.primitives.values())
    findings: List[Finding] = []
    findings += _check_float_lane(entry, built, closed)
    findings += _check_callbacks(entry, built, closed)
    findings += _check_transfers(entry, built, closed)
    findings += _check_weak_types(entry, built, closed)
    findings += _check_allowlist(entry, built, closed, audit.primitives)
    audit.findings, audit.suppressed = _apply_suppressions(
        entry, built, findings, pragma_cache)
    return audit


class _pinned_xla_tier:
    """Pin the fallback policy to the XLA tier for the audit's span.

    The audited program shapes must be deterministic per jax version,
    not per machine: on a TPU-attached host the policy would route the
    plugin surfaces through Pallas/MXU and every allowlist would
    differ from the CPU CI run.  The audit therefore certifies the
    platform-independent XLA-tier programs everywhere, and reaches the
    TPU-only tiers explicitly — the Pallas kernels in interpret mode
    and the MXU matmul directly (ops.apply_matrix_mxu, float_ok)."""

    def __enter__(self):
        from ..ops.fallback import FallbackPolicy, set_global_policy

        self._restore = set_global_policy
        self._prev = set_global_policy(FallbackPolicy(force="xla"))
        return self

    def __exit__(self, *exc):
        self._restore(self._prev)


def audit_registry(entries: Optional[Sequence[EntryPoint]] = None,
                   sentinel: bool = True,
                   completeness: bool = True) -> TraceReport:
    """Audit every registered entry point: trace rules + (optionally)
    the recompile sentinel + the registry-completeness gate.  Runs
    under the pinned XLA engine tier (see _pinned_xla_tier)."""
    entries = list(entries) if entries is not None else list(registry())
    with _pinned_xla_tier():
        return _audit_registry_pinned(entries, sentinel, completeness)


def _audit_registry_pinned(entries, sentinel: bool,
                           completeness: bool) -> TraceReport:
    pragma_cache: Dict[str, PragmaInfo] = {}
    audits: List[EntryAudit] = []
    for entry in entries:
        try:
            built = entry.build()
        except Exception as e:  # noqa: BLE001 — reported, never swallowed
            bad = EntryAudit(entry.name, entry.family, entry.kind, [], [])
            bad.findings.append(_finding(
                entry, None, ERROR_RULE,
                f"build failed: {type(e).__name__}: {e}"))
            audits.append(bad)
            continue
        audit = audit_entry_point(entry, built, pragma_cache)
        if sentinel:
            s = run_sentinel(entry, built, pragma_cache)
            audit.cold_compiles = s.cold_compiles
            audit.warm_compiles = s.warm_compiles
            audit.findings += s.findings
            audit.suppressed += s.suppressed
        audits.append(audit)
    gaps = registry_gaps() if completeness else []
    return TraceReport(audits, gaps, pragma_cache)


def stale_trace_pragmas(paths: Sequence[str],
                        report: TraceReport) -> List[Finding]:
    """``disable=audit-*`` pragmas under ``paths`` that suppressed
    nothing during ``report``'s run — the trace half of
    ``--check-suppressions`` (the AST half lives in scanner.py).

    A file no entry point anchors to cannot legitimately carry an
    audit pragma at all, so every audit rule it names is stale."""
    from .scanner import iter_python_files

    stale: List[Finding] = []
    for path in iter_python_files(paths):
        key = os.path.abspath(path)
        info = report.pragmas.get(key)
        if info is None:
            info = _pragmas_for(path, report.pragmas)
        for s in info.suppressions:
            for rule in sorted(r for r in s.rules
                               if r.startswith("audit-")
                               and r not in s.used_rules):
                line = s.line or 1
                reason = f" -- {s.reason}" if s.reason else ""
                stale.append(Finding(
                    "stale-suppression", path, line, 0, line,
                    f"suppression for trace rule '{rule}' no longer "
                    f"matches any audit finding{reason}"))
    report.stale = stale
    return stale
