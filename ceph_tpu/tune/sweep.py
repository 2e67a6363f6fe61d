"""Profiler-driven config search — the two sweep modes (ISSUE 14).

The tuner owns no measurement machinery of its own: it reuses the two
modes the device-plane profiler (telemetry/profiler.py, ISSUE 10)
already owns —

- **analytic** (``analytic_sweep``): the GF(2^8) cost models
  (``analytic_matrix_cost`` / ``analytic_xor_schedule_cost``) priced
  through a roofline — modeled time = max(HBM time, op time at the
  tier's modeled op rate) — with ZERO jax compiles and zero device
  arrays, so it works without a device and inside the ``tune.sweep``
  host-tier audit entry.  Deterministic given the seed (the property
  tests/test_autotune.py pins).
- **timed** (``timed_sweep``): real min-of-N eager dispatches of the
  candidate programs, with lower-only ``cost_analysis`` capture riding
  each candidate exactly like the engine seams do (zero *extra*
  backend compiles; the candidate programs themselves compile once,
  like any cold program).  Byte-identity across every candidate tier
  is asserted in-sweep — a tuned config that changed bytes would be a
  bug, not a win.

Both modes emit **before/after utilization rows through
``ProgramProfiler.attribution_rows()``** — the gain is measured (or
modeled) by the same instrument the bench reports with, not claimed —
and persist winners in a :class:`~ceph_tpu.tune.table.BestConfigTable`
(tune/table.py) keyed per (plugin profile, pattern kind, engine tier,
layout, device_count, batch rung).

The work-unit corpus comes from the tpu-audit registry's
representative profiles (analysis/entrypoints.py), so every tuned row
names a registered entry-point family.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry.profiler import (ProgramProfiler, analytic_matrix_cost,
                                  analytic_xor_schedule_cost,
                                  resolve_peak_gbps)
from . import space as tspace
from .table import (BestConfigTable, current_env, key_str, matrix_digest,
                    profile_str, tuning_key, validate_table)

# ----------------------------------------------------------------------
# the roofline model's op-rate constants (G byte-ops/s) per jax
# device_kind.  These are MODEL constants, not kernel claims: the
# sweep only ever compares candidates under ONE consistent model, so
# the decisions depend on the ratios, not the absolute numbers.  A
# kind with no entry has no model: the lookup raises.
VPU_BYTE_GOPS: Dict[str, float] = {"TPU v5 lite": 8192.0}
MXU_BYTE_GOPS: Dict[str, float] = {"TPU v5 lite": 180000.0}
# the chip the analytic sweep models, whichever backend runs it
MODEL_DEVICE_KIND = "TPU v5 lite"
# the XLA dense path materializes doubling planes between fusions
# (ops/pallas_gf.py module docstring) — modeled as an op-rate penalty
XLA_DENSE_PENALTY = 2.0
# modeled per-grid-step launch overhead + per-dispatch overhead
GRID_STEP_OVH_S = 2e-6
DISPATCH_OVH_S = 2e-4
# VMEM working-set budget for the row-tile model (v5e: 16 MiB/core,
# half budgeted for double-buffering)
VMEM_BUDGET_BYTES = 8 << 20

LANE = 128
SUBLANE_U8 = 32


def _rate(table: Dict[str, float], device_kind: str, what: str) -> float:
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(f"no modeled {what} rate for device kind "
                         f"{device_kind!r}") from None


def vpu_gops(device_kind: str) -> float:
    return _rate(VPU_BYTE_GOPS, device_kind, "VPU")


def mxu_gops(device_kind: str) -> float:
    return _rate(MXU_BYTE_GOPS, device_kind, "MXU")


def model_peak_gbps(device_kind: str) -> float:
    peak = resolve_peak_gbps(device_kind)
    if peak is None:
        raise ValueError(f"no published HBM peak for device kind "
                         f"{device_kind!r}")
    return peak


def modeled_time_s(ops: float, bytes_accessed: float, peak_gbps: float,
                   gops: float) -> float:
    """Roofline: the program takes the longer of its HBM stream and
    its op stream."""
    return max(bytes_accessed / (peak_gbps * 1e9), ops / (gops * 1e9))


# ----------------------------------------------------------------------
# the work-unit corpus (from the tpu-audit registry's representative
# profiles — every tuned row names an audited entry-point family)

@dataclasses.dataclass(frozen=True)
class WorkUnit:
    """One tunable program family: a static matrix + its workload
    coordinates (the same slots the tuning key speaks)."""
    name: str              # "jerasure.decode_chunks_jax" style
    profile: str           # tune.table.profile_str form
    kind: str              # "serve-encode" | "serve-decode"
    matrix: tuple          # the static (r, s) GF(2^8) matrix
    chunk: int
    batch: int


def _corpus_instance(family: str):
    """A corpus plugin instance pinned OFF the XLA path (the analytic
    sweep and the audit selftest must never dispatch jax; the impulse
    probes underneath are tiny and ride the numpy tier anyway)."""
    from ..analysis.entrypoints import REPRESENTATIVE_PROFILES
    from ..codes.registry import ErasureCodePluginRegistry
    plugin, profile = REPRESENTATIVE_PROFILES[family]
    ec = ErasureCodePluginRegistry.instance().factory(
        plugin, dict(profile))
    ec.min_xla_bytes = float("inf")
    return ec, plugin, profile


def _decode_matrix_static(ec, available, erased):
    """The static decode matrix an (available, erased) pattern runs —
    shared with the bench's metric_version 9 provenance probe."""
    from ..bench.erasure_code_benchmark import ErasureCodeBench
    return ErasureCodeBench._decode_matrix_static(ec, available, erased)


def corpus(families: Sequence[str] = ("jerasure", "shec", "lrc",
                                      "clay"),
           chunk: int = 4096, batch: int = 16) -> List[WorkUnit]:
    """Representative work units: each family's encode matrix and its
    single-erasure decode matrix (the patterns the audit registry's
    builders exercise).  Families whose matrix surfaces are not
    probeable host-side are skipped loudly (returned corpus is still
    deterministic)."""
    from ..ops.xla_ops import matrix_to_static
    units: List[WorkUnit] = []
    for family in families:
        try:
            ec, plugin, profile = _corpus_instance(family)
        except Exception:  # noqa: BLE001 — a missing family shrinks
            continue       # the corpus, never kills the sweep
        prof = profile_str(plugin, profile)
        enc = getattr(ec, "matrix", None)
        if enc is None:
            probe = getattr(ec, "_probe_encode_matrix", None)
            if probe is not None:
                try:
                    out = probe()
                    enc = out[0] if isinstance(out, tuple) else out
                except Exception:  # noqa: BLE001
                    enc = None
        if enc is not None and getattr(ec, "w", 8) == 8:
            units.append(WorkUnit(
                name=f"{family}.encode_chunks_jax", profile=prof,
                kind="serve-encode", matrix=matrix_to_static(enc),
                chunk=chunk, batch=batch))
        n = ec.get_chunk_count()
        available = tuple(i for i in range(n) if i != 1)
        try:
            ms = _decode_matrix_static(ec, available, (1,))
        except Exception:  # noqa: BLE001
            ms = None
        if ms is not None:
            units.append(WorkUnit(
                name=f"{family}.decode_chunks_jax", profile=prof,
                kind="serve-decode", matrix=ms, chunk=chunk,
                batch=batch))
    return units


# ----------------------------------------------------------------------
# per-tier cost model (the analytic side of the matrix-engine sweep)

def tier_cost(matrix: tuple, tier: str, batch: int, chunk: int,
              device_kind: str = MODEL_DEVICE_KIND,
              topk: Optional[int] = None
              ) -> Optional[Tuple[float, float, float]]:
    """(ops, bytes_accessed, gops) for one tier running one matrix, or
    None when the tier cannot run it.  ops/bytes speak the profiler's
    analytic-model currency, gops the tier's modeled op rate."""
    from ..ops.xor_schedule import build_schedule, dense_vpu_cost
    r, s = len(matrix), len(matrix[0])
    bytes_acc = analytic_matrix_cost(batch, r, s, chunk)[
        "bytes accessed"]
    if tier == "xor":
        from ..ops.xor_schedule import probe_schedule
        sched = probe_schedule(matrix, 8)
        if sched is None:
            return None
        if topk is not None:
            sched = build_schedule(matrix, 8, topk=topk)
        ops = analytic_xor_schedule_cost(batch, r, s, chunk,
                                         sched.vpu_ops)["flops"]
        return ops, bytes_acc, vpu_gops(device_kind)
    if tier == "mxu":
        # the bit-sliced GF(2) matmul: an (8r x 8s) contraction per
        # byte — 2*64*r*s ops/byte at the MXU's modeled rate
        ops = 2.0 * 64 * r * s * chunk * batch
        return ops, bytes_acc, mxu_gops(device_kind)
    if tier in ("pallas", "xla"):
        ops = float(dense_vpu_cost(matrix)) * chunk * batch
        gops = vpu_gops(device_kind)
        if tier == "xla":
            gops /= XLA_DENSE_PENALTY
        return ops, bytes_acc, gops
    return None


def heuristic_tier(matrix: tuple, platform: str,
                   mxu_min: Optional[int] = None,
                   cutover: Optional[Tuple[int, int]] = None) -> str:
    """The tier today's hand-picked heuristics route ``matrix`` to on
    ``platform`` — the sweep's baseline (mirrors
    select_matrix_engine's xor/mxu/pallas/xla ladder under an
    explicit threshold override)."""
    from ..ops.pallas_gf import MXU_MATRIX_MIN
    from ..ops.xor_schedule import XOR_DENSE_CUTOVER, probe_schedule
    mxu_min = MXU_MATRIX_MIN if mxu_min is None else mxu_min
    num, den = XOR_DENSE_CUTOVER if cutover is None else cutover
    nnz = sum(1 for row in matrix for v in row if v)
    sched = probe_schedule(matrix, 8)
    if sched is not None and sched.vpu_ops * den <= num * sched.dense_vpu_ops:
        if not (nnz >= mxu_min and sched.vpu_ops >= nnz):
            return "xor"
    if platform == "tpu":
        return "mxu" if nnz >= mxu_min else "pallas"
    return "xla"


# ----------------------------------------------------------------------
# the sweep report

@dataclasses.dataclass
class SweepReport:
    """One sweep's output: the best-config table plus the before/after
    utilization rows (the instrument's own attribution rows underneath
    in ``attribution``)."""
    mode: str
    seed: int
    platform: str
    device_count: int
    table: BestConfigTable
    rows: List[dict] = dataclasses.field(default_factory=list)
    attribution: List[dict] = dataclasses.field(default_factory=list)
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def improved(self) -> List[dict]:
        return [r for r in self.rows
                if (r.get("improvement_pct") or 0) > 0]

    def headline(self) -> Optional[dict]:
        """The most-improved row (the bench autotune row's payload)."""
        rows = self.improved
        if not rows:
            return None
        return max(rows, key=lambda r: (r["improvement_pct"], r["name"]))

    def to_dict(self) -> dict:
        errors = validate_table(self.table.to_dict())
        return {
            "mode": self.mode,
            "seed": self.seed,
            "platform": self.platform,
            "device_count": self.device_count,
            "tuned_keys": sorted(self.table.entries),
            "table": self.table.to_dict(),
            "table_valid": not errors,
            "rows": self.rows,
            "attribution": self.attribution,
            "notes": sorted(self.notes),
        }


def _ba_row(prof: ProgramProfiler, unit_name: str, key: Tuple,
            kind: str, before: dict, after: dict) -> dict:
    """One before/after row, utilization read back FROM the profiler's
    attribution join (never recomputed here)."""
    util = {}
    for row in prof.attribution_rows():
        util[(row["name"], row.get("phase"))] = row
    b = util.get((unit_name, "before"), {})
    a = util.get((unit_name, "after"), {})
    t0 = before.get("modeled_ms") or before.get("p50_ms")
    t1 = after.get("modeled_ms") or after.get("p50_ms")
    imp = None
    if t0 and t1 and t0 > 0:
        imp = round(100.0 * (t0 - t1) / t0, 2)
    return {
        "name": unit_name,
        "key": key_str(key),
        "kind": kind,
        "before": {**before,
                   "utilization_pct": b.get("utilization_pct")},
        "after": {**after,
                  "utilization_pct": a.get("utilization_pct")},
        "improvement_pct": imp,
    }


# ----------------------------------------------------------------------
# analytic mode

def analytic_sweep(seed: int = 0, platform: Optional[str] = None,
                   device_count: Optional[int] = None,
                   chunk: int = 4096, batch: int = 16,
                   families: Sequence[str] = ("jerasure", "shec",
                                              "lrc", "clay"),
                   device_kind: str = MODEL_DEVICE_KIND,
                   ) -> SweepReport:
    """The host-only sweep: zero jax compiles, zero device arrays,
    byte-identical output from one seed.  Sweeps every kind in
    tune/space.py against the representative corpus under the
    roofline model of ``device_kind`` (op rates and HBM peak; tiers
    limited to what ``platform`` can dispatch) and returns the table +
    before/after rows."""
    env = current_env()
    platform = platform or env["platform"]
    device_count = device_count if device_count is not None \
        else env["device_count"]
    peak = model_peak_gbps(device_kind)
    table = BestConfigTable(env={**env, "platform": platform,
                                 "device_count": device_count})
    prof = ProgramProfiler(clock=_NullClock())
    report = SweepReport(mode="analytic", seed=seed, platform=platform,
                         device_count=device_count, table=table)
    units = corpus(families, chunk=chunk, batch=batch)
    if not units:
        report.notes.append("empty corpus")
        return report

    # -- per-matrix engine pins (kind: matrix-engine) -------------------
    for unit in units:
        cands = {}
        for tier in tspace.space("matrix-engine")["engine"]:
            if tier in ("pallas", "mxu") and platform != "tpu":
                continue
            tc = tier_cost(unit.matrix, tier, unit.batch, unit.chunk,
                           device_kind)
            if tc is None:
                continue
            ops, byts, gops = tc
            cands[tier] = (modeled_time_s(ops, byts, peak, gops),
                           ops, byts)
        if not cands:
            continue
        base_tier = heuristic_tier(unit.matrix, platform)
        if base_tier not in cands:
            base_tier = min(sorted(cands), key=lambda t: cands[t][0])
        # ties keep the baseline: a pin must WIN, not reshuffle equals
        best_tier = min(sorted(cands),
                        key=lambda t: (cands[t][0], t != base_tier, t))
        key = tuning_key("m:" + matrix_digest(unit.matrix),
                         "matrix-engine", "*", "bytes", device_count,
                         0)
        for phase, tier in (("before", base_tier), ("after", best_tier)):
            t, ops, byts = cands[tier]
            pk = (unit.name, phase)
            prof.capture(pk, name=unit.name, device_kind=device_kind,
                         cost={"flops": ops, "bytes accessed": byts},
                         arg_bytes=unit.batch * len(unit.matrix[0])
                         * unit.chunk,
                         plugin=unit.profile, kind=unit.kind,
                         engine=tier, phase=phase, devices=1,
                         source_mode="analytic")
            prof.observe(pk, t)
        gain = cands[base_tier][0] / cands[best_tier][0]
        if best_tier != base_tier and gain >= 1.05:
            table.set(key, {"engine": best_tier}, mode="analytic",
                      score=cands[best_tier][0],
                      baseline_score=cands[base_tier][0],
                      baseline_config={"engine": base_tier})
        report.rows.append(_ba_row(
            prof, unit.name, key, "matrix-engine",
            {"engine": base_tier,
             "modeled_ms": round(cands[base_tier][0] * 1e3, 6)},
            {"engine": best_tier,
             "modeled_ms": round(cands[best_tier][0] * 1e3, 6)}))

    # -- global thresholds (kind: engine-select) ------------------------
    def routing_cost(cfg: dict) -> float:
        total = 0.0
        for unit in units:
            tier = heuristic_tier(unit.matrix, platform,
                                  mxu_min=cfg["mxu_matrix_min"],
                                  cutover=tuple(cfg["xor_cutover"]))
            tc = tier_cost(unit.matrix, tier, unit.batch, unit.chunk,
                           device_kind)
            if tc is None:
                tc = tier_cost(unit.matrix, "xla", unit.batch,
                               unit.chunk, device_kind)
            ops, byts, gops = tc
            total += modeled_time_s(ops, byts, peak, gops)
        return total

    default_sel = tspace.default_config("engine-select")
    base_cost = routing_cost(default_sel)
    best_sel, best_cost = default_sel, base_cost
    for cand in tspace.candidates("engine-select"):
        c = routing_cost(cand)
        if c < best_cost:
            best_sel, best_cost = cand, c
    sel_key = tuning_key("*", "engine-select", "*", "*", device_count, 0)
    if best_sel != default_sel:
        table.set(sel_key,
                  {"mxu_matrix_min": best_sel["mxu_matrix_min"],
                   "xor_cutover": list(best_sel["xor_cutover"])},
                  mode="analytic", score=best_cost,
                  baseline_score=base_cost,
                  baseline_config={
                      "mxu_matrix_min": default_sel["mxu_matrix_min"],
                      "xor_cutover": list(default_sel["xor_cutover"])})
    report.rows.append({
        "name": "engine-select", "key": key_str(sel_key),
        "kind": "engine-select",
        "before": {"config": {k: list(v) if isinstance(v, tuple) else v
                              for k, v in default_sel.items()},
                   "modeled_ms": round(base_cost * 1e3, 6)},
        "after": {"config": {k: list(v) if isinstance(v, tuple) else v
                             for k, v in best_sel.items()},
                  "modeled_ms": round(best_cost * 1e3, 6)},
        "improvement_pct": round(100.0 * (base_cost - best_cost)
                                 / base_cost, 2) if base_cost else None,
    })

    # -- CSE candidate horizon (kind: xor-schedule) ---------------------
    from ..ops.xor_schedule import CSE_TOPK, build_schedule
    sched_units = [u for u in units
                   if tier_cost(u.matrix, "xor", u.batch, u.chunk,
                                device_kind) is not None]
    if sched_units:
        def topk_ops(topk: int) -> int:
            return sum(build_schedule(u.matrix, 8, topk=topk).vpu_ops
                       for u in sched_units)

        base_ops = topk_ops(CSE_TOPK)
        best_topk, best_ops = CSE_TOPK, base_ops
        for cand in tspace.candidates("xor-schedule"):
            ops = topk_ops(cand["cse_topk"])
            if ops < best_ops or (ops == best_ops
                                  and cand["cse_topk"] < best_topk):
                best_topk, best_ops = cand["cse_topk"], ops
        topk_key = tuning_key("*", "xor-schedule", "*", "*",
                              device_count, 0)
        if best_topk != CSE_TOPK and best_ops < base_ops:
            table.set(topk_key, {"cse_topk": best_topk},
                      mode="analytic", score=float(best_ops),
                      baseline_score=float(base_ops),
                      baseline_config={"cse_topk": CSE_TOPK})
        report.rows.append({
            "name": "xor-schedule.cse_topk", "key": key_str(topk_key),
            "kind": "xor-schedule",
            "before": {"config": {"cse_topk": CSE_TOPK},
                       "vpu_ops": base_ops},
            "after": {"config": {"cse_topk": best_topk},
                      "vpu_ops": best_ops},
            "improvement_pct": round(100.0 * (base_ops - best_ops)
                                     / base_ops, 2) if base_ops else None,
        })

    # -- row-tile cap (kind: row-tile, per layout) ----------------------
    big_chunk = 1 << 20
    rows8 = big_chunk // LANE
    s_rep, r_rep = 8, 3          # the north-star RS shape
    for layout in ("bytes", "packed"):
        def tile_time(cap: int) -> Optional[float]:
            rt = 0
            for c in range(cap, SUBLANE_U8 - 1, -SUBLANE_U8):
                if c <= rows8 and rows8 % c == 0:
                    rt = c
                    break
            if rt == 0:
                return None
            tile_bytes = (s_rep + r_rep) * rt * LANE
            if tile_bytes > VMEM_BUDGET_BYTES:
                return None
            steps = rows8 // rt
            byts = (s_rep + r_rep) * big_chunk
            return steps * GRID_STEP_OVH_S + byts / (peak * 1e9)

        default_cap = tspace.default_config("row-tile")["max_row_tile8"]
        base_t = tile_time(default_cap)
        best_cap, best_t = default_cap, base_t
        for cand in tspace.candidates("row-tile"):
            t = tile_time(cand["max_row_tile8"])
            if t is not None and (best_t is None or t < best_t):
                best_cap, best_t = cand["max_row_tile8"], t
        cap_key = tuning_key("*", "row-tile", "pallas", layout,
                             device_count, 0)
        if best_cap != default_cap and base_t and best_t < base_t:
            table.set(cap_key, {"max_row_tile8": best_cap},
                      mode="analytic", score=best_t,
                      baseline_score=base_t,
                      baseline_config={"max_row_tile8": default_cap})
        report.rows.append({
            "name": f"row-tile.{layout}", "key": key_str(cap_key),
            "kind": "row-tile",
            "before": {"config": {"max_row_tile8": default_cap},
                       "modeled_ms": round(base_t * 1e3, 6)
                       if base_t else None},
            "after": {"config": {"max_row_tile8": best_cap},
                      "modeled_ms": round(best_t * 1e3, 6)
                      if best_t else None},
            "improvement_pct": round(100.0 * (base_t - best_t)
                                     / base_t, 2)
            if base_t and best_t else None,
        })

    # -- serve batch rung ladder (kind: serve-ladder) -------------------
    rng = np.random.default_rng(seed)
    top = max(max(lad) for lad in
              tspace.space("serve-ladder")["ladder"])
    occupancies = [int(v) for v in
                   rng.integers(1, top + 1, size=256)]

    def ladder_score(ladder: Tuple[int, ...]) -> Tuple[float, float]:
        stripes = padded = 0
        for occ in occupancies:
            n = occ
            while n > 0:
                take = min(n, ladder[-1])
                rung = next(r for r in ladder if take <= r)
                stripes += take
                padded += rung - take
                n -= take
        frac = padded / (stripes + padded)
        # |ladder| warm programs per bucket is a (small) modeled cost
        return frac + 0.002 * len(ladder), \
            round(100.0 * stripes / (stripes + padded), 4)

    default_lad = tuple(tspace.default_config("serve-ladder")["ladder"])
    base_score, base_util = ladder_score(default_lad)
    best_lad, best_score, best_util = default_lad, base_score, base_util
    for cand in tspace.candidates("serve-ladder"):
        lad = tuple(cand["ladder"])
        sc, ut = ladder_score(lad)
        if sc < best_score:
            best_lad, best_score, best_util = lad, sc, ut
    lad_key = tuning_key("*", "serve-ladder", "*", "*", device_count, 0)
    if best_lad != default_lad:
        table.set(lad_key, {"ladder": list(best_lad)},
                  mode="analytic", score=best_score,
                  baseline_score=base_score,
                  baseline_config={"ladder": list(default_lad)})
    report.rows.append({
        "name": "serve-ladder", "key": key_str(lad_key),
        "kind": "serve-ladder",
        "before": {"config": {"ladder": list(default_lad)},
                   "utilization_pct": base_util},
        "after": {"config": {"ladder": list(best_lad)},
                  "utilization_pct": best_util},
        "improvement_pct": round(100.0 * (base_score - best_score)
                                 / base_score, 2)
        if base_score else None,
    })

    # -- mesh fan-out width (kind: mesh-fanout) -------------------------
    if device_count > 1:
        rep_bytes = 64 * (s_rep + r_rep) * (1 << 18)

        def fanout_time(n: int) -> float:
            return DISPATCH_OVH_S + rep_bytes / (n * peak * 1e9)

        cands_n = [n for c in tspace.candidates("mesh-fanout")
                   for n in (c["n_devices"],) if n <= device_count]
        if cands_n:
            best_n = min(sorted(cands_n), key=fanout_time)
            fan_key = tuning_key("*", "mesh-fanout", "mesh", "*",
                                 device_count, 0)
            base_t, best_t = fanout_time(device_count), \
                fanout_time(best_n)
            if best_n != device_count:
                table.set(fan_key, {"n_devices": best_n},
                          mode="analytic", score=best_t,
                          baseline_score=base_t,
                          baseline_config={"n_devices": device_count})
            report.rows.append({
                "name": "mesh-fanout", "key": key_str(fan_key),
                "kind": "mesh-fanout",
                "before": {"config": {"n_devices": device_count},
                           "modeled_ms": round(base_t * 1e3, 6)},
                "after": {"config": {"n_devices": best_n},
                          "modeled_ms": round(best_t * 1e3, 6)},
                "improvement_pct": round(100.0 * (base_t - best_t)
                                         / base_t, 2),
            })

    report.attribution = prof.attribution_rows()
    return report


class _NullClock:
    """The analytic profiler never reads a clock (observations are
    modeled times); a zero clock keeps the report byte-identical."""

    def monotonic(self) -> float:
        return 0.0


# ----------------------------------------------------------------------
# timed mode (min-of-N eager dispatch + lower-only cost capture)

def timed_sweep(plugin: str = "jerasure",
                profile: Optional[Dict[str, str]] = None,
                size: int = 1 << 18, batch: int = 16,
                repeats: int = 3, seed: int = 42) -> SweepReport:
    """Measure the candidate tiers (and, on TPU, row-tile caps) with
    real dispatches: min-of-N wall time per candidate, byte-identity
    asserted across every candidate against the default tier's
    output.  Requires a live jax backend; :func:`analytic_sweep` is
    the device-free mode (the bench wires both)."""
    import jax

    from ..codes.registry import ErasureCodePluginRegistry
    from ..ops import pallas_gf
    from ..ops.xla_ops import matrix_to_static

    if profile is None:
        profile = {"technique": "reed_sol_van", "k": "4", "m": "2"}
    env = current_env()
    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    device_count = jax.device_count()
    table = BestConfigTable(env={**env, "platform": platform,
                                 "device_count": device_count})
    prof = ProgramProfiler()
    report = SweepReport(mode="timed", seed=seed, platform=platform,
                         device_count=device_count, table=table)

    ec = ErasureCodePluginRegistry.instance().factory(
        plugin, dict(profile))
    pstr = profile_str(plugin, profile)
    n = ec.get_chunk_count()
    chunk = ec.get_chunk_size(size)
    available = tuple(i for i in range(n) if i != 1)
    units = [WorkUnit(f"{plugin}.encode_chunks_jax", pstr,
                      "serve-encode", matrix_to_static(ec.matrix),
                      chunk, batch)]
    ms = _decode_matrix_static(ec, available, (1,))
    if ms is not None:
        units.append(WorkUnit(f"{plugin}.decode_chunks_jax", pstr,
                              "serve-decode", ms, chunk, batch))

    rng = np.random.default_rng(seed)
    for unit in units:
        s = len(unit.matrix[0])
        x = jax.device_put(rng.integers(
            0, 256, size=(unit.batch, s, unit.chunk), dtype=np.uint8))
        cands = ["xla"]
        from ..ops.xor_schedule import probe_schedule
        if probe_schedule(unit.matrix, 8) is not None:
            cands.append("xor")
        cands.append("mxu")        # the bit-plane einsum runs anywhere
        if pallas_gf.use_pallas() and \
                pallas_gf.pallas_matrix_padded_supported(
                    (unit.batch, s, unit.chunk), 8):
            cands.append("pallas")
        timings: Dict[str, float] = {}
        outputs: Dict[str, np.ndarray] = {}
        for tier in cands:
            def fn(v, _t=tier, _m=unit.matrix):
                return pallas_gf._run_matrix_bytes(v, _m, 8, _t)
            try:
                out = jax.block_until_ready(fn(x))   # compile + warm
            except Exception as e:  # noqa: BLE001 — a tier that
                # cannot dispatch here is excluded, not fatal
                report.notes.append(
                    f"{unit.name}:{tier}: {type(e).__name__}: {e}")
                continue
            outputs[tier] = np.asarray(out)
            pk = (unit.name, tier)
            prof.capture(pk, jax.jit(fn), (x,), name=unit.name,
                         device_kind=device_kind, plugin=unit.profile,
                         kind=unit.kind, engine=tier, phase=tier,
                         devices=device_count, source_mode="timed")
            best = None
            for _ in range(max(2, repeats)):
                t0 = prof.clock.monotonic()
                jax.block_until_ready(fn(x))
                dt = prof.clock.monotonic() - t0
                prof.observe(pk, dt)
                best = dt if best is None else min(best, dt)
            timings[tier] = best
        if not timings:
            continue
        # byte-identity across every candidate tier — a tuned config
        # may only ever change WHERE the bytes are computed
        ref_tier = sorted(outputs)[0]
        for tier, out in sorted(outputs.items()):
            if not np.array_equal(out, outputs[ref_tier]):
                raise AssertionError(
                    f"{unit.name}: tier {tier} diverged from "
                    f"{ref_tier} — tuned configs must be "
                    f"byte-identical")
        base_tier = pallas_gf.select_matrix_engine(
            (unit.batch, s, unit.chunk), unit.matrix, 8, mesh=0)
        if base_tier not in timings:
            base_tier = min(sorted(timings), key=lambda t: timings[t])
        # ties keep the baseline: a pin must WIN, not reshuffle equals
        best_tier = min(sorted(timings),
                        key=lambda t: (timings[t], t != base_tier, t))
        key = tuning_key("m:" + matrix_digest(unit.matrix),
                         "matrix-engine", "*", "bytes", device_count,
                         0)
        # re-key the winner/baseline pair into before/after rows so
        # attribution_rows() carries the same phases as analytic mode
        for phase, tier in (("before", base_tier), ("after", best_tier)):
            src = (unit.name, tier)
            pk = (unit.name, "ba", phase)
            rec = None
            for r in prof.attribution_rows():
                if r["name"] == unit.name and r.get("phase") == tier:
                    rec = r
                    break
            prof.capture(pk, name=unit.name, device_kind=device_kind,
                         cost={"flops": (rec or {}).get("flops") or 0.0,
                               "bytes accessed":
                               (rec or {}).get("bytes_accessed")
                               or 0.0},
                         arg_bytes=int(x.nbytes),
                         plugin=unit.profile, kind=unit.kind,
                         engine=tier, phase=phase,
                         devices=device_count, source_mode="timed")
            prof.observe(pk, timings[tier])
        gain = timings[base_tier] / timings[best_tier]
        if best_tier != base_tier and gain >= 1.05:
            table.set(key, {"engine": best_tier}, mode="timed",
                      score=timings[best_tier],
                      baseline_score=timings[base_tier],
                      baseline_config={"engine": base_tier})
        report.rows.append(_ba_row(
            prof, unit.name, key, "matrix-engine",
            {"engine": base_tier,
             "p50_ms": round(timings[base_tier] * 1e3, 6)},
            {"engine": best_tier,
             "p50_ms": round(timings[best_tier] * 1e3, 6)}))

    # row-tile caps, measured (TPU only: the cap is a Pallas tiling
    # parameter; elsewhere the analytic model's entry stands)
    if pallas_gf.use_pallas():
        rt_unit = units[0]
        s = len(rt_unit.matrix[0])
        x = jax.device_put(rng.integers(
            0, 256, size=(rt_unit.batch, s, rt_unit.chunk),
            dtype=np.uint8))
        default_cap = tspace.default_config("row-tile")["max_row_tile8"]
        timings = {}
        for cand in tspace.candidates("row-tile"):
            cap = cand["max_row_tile8"]
            try:
                jax.block_until_ready(pallas_gf.apply_matrix_pallas(
                    x, rt_unit.matrix, False, cap))
            except Exception as e:  # noqa: BLE001
                report.notes.append(f"row-tile:{cap}: "
                                    f"{type(e).__name__}: {e}")
                continue
            best = None
            for _ in range(max(2, repeats)):
                t0 = prof.clock.monotonic()
                jax.block_until_ready(pallas_gf.apply_matrix_pallas(
                    x, rt_unit.matrix, False, cap))
                dt = prof.clock.monotonic() - t0
                best = dt if best is None else min(best, dt)
            timings[cap] = best
        if timings:
            base_t = timings.get(default_cap)
            best_cap = min(sorted(timings), key=lambda c: timings[c])
            cap_key = tuning_key("*", "row-tile", "pallas", "bytes",
                                 device_count, 0)
            if base_t and best_cap != default_cap \
                    and timings[best_cap] < base_t:
                table.set(cap_key, {"max_row_tile8": best_cap},
                          mode="timed", score=timings[best_cap],
                          baseline_score=base_t,
                          baseline_config={"max_row_tile8":
                                           default_cap})
            report.rows.append({
                "name": "row-tile.bytes", "key": key_str(cap_key),
                "kind": "row-tile",
                "before": {"config": {"max_row_tile8": default_cap},
                           "p50_ms": round(base_t * 1e3, 6)
                           if base_t else None},
                "after": {"config": {"max_row_tile8": best_cap},
                          "p50_ms": round(timings[best_cap] * 1e3, 6)},
                "improvement_pct": round(
                    100.0 * (base_t - timings[best_cap]) / base_t, 2)
                if base_t else None,
            })

    report.attribution = prof.attribution_rows()
    return report


# ----------------------------------------------------------------------
# the tpu-audit host-tier workload (analysis/entrypoints.py tune.sweep)

def tune_sweep_selftest() -> dict:
    """The ``tune.sweep`` host-tier audit entry: a seeded analytic
    sweep over the two numpy-cheapest corpus families, twice, with the
    results pinned byte-identical and the emitted table schema-valid —
    ZERO jax compiles and zero device arrays, forever (the recompile
    sentinel enforces it)."""
    import json

    kwargs = dict(seed=7, platform="cpu", device_count=1,
                  chunk=2048, batch=4, families=("jerasure", "shec"))
    rep1 = analytic_sweep(**kwargs)
    rep2 = analytic_sweep(**kwargs)
    d1, d2 = rep1.to_dict(), rep2.to_dict()
    if json.dumps(d1, sort_keys=True) != json.dumps(d2, sort_keys=True):
        raise AssertionError("analytic sweep is not deterministic")
    errors = validate_table(rep1.table.to_dict())
    if errors:
        raise AssertionError(f"sweep table invalid: {errors}")
    if not rep1.rows:
        raise AssertionError("analytic sweep produced no rows")
    for row in rep1.rows:
        if "before" not in row or "after" not in row:
            raise AssertionError(f"row missing before/after: {row}")
    roundtrip = BestConfigTable.from_dict(rep1.table.to_dict())
    if roundtrip.to_json() != rep1.table.to_json():
        raise AssertionError("table does not round-trip")
    return d1


__all__ = [
    "MODEL_DEVICE_KIND", "MXU_BYTE_GOPS", "SweepReport",
    "VPU_BYTE_GOPS", "WorkUnit",
    "analytic_sweep", "corpus", "heuristic_tier", "modeled_time_s",
    "tier_cost", "timed_sweep", "tune_sweep_selftest",
]
