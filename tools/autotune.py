#!/usr/bin/env python3
"""autotune — roofline-closing config search, persisted (ISSUE 14).

Sweeps the bounded declarative config space (ceph_tpu/tune/space.py)
— row-tile caps, MXU/XOR/dense cutover thresholds, CSE horizon, serve
rung ladder, mesh fan-out, per-matrix engine pins — with the two
measurement modes the device-plane profiler already owns, and
persists winners in a versioned, schema-validated best-config table
(ceph_tpu/tune/table.py) the engine's consultation seams read at
program-build time.

1. **Baseline first** — the run opens with
   ``attribution_rows()`` utilization baselines for the hottest
   programs (timed mode drives the engine's cached programs to
   populate them; analytic mode prints the model's "before" side), so
   the gain is measured by the instrument, not claimed.
2. **Sweep** — ``--analytic`` prices every candidate under the
   GF(2^8) roofline model with ZERO jax compiles (the test_full.sh
   smoke gate); the default timed mode
   runs min-of-N eager dispatches per candidate with lower-only
   ``cost_analysis`` capture, asserting byte-identity across every
   candidate tier.
3. **Persist** — winners land in ``--out`` (atomic write).  Point
   ``CEPH_TPU_TUNE_TABLE=<path>`` at the file and every later process
   consults it — same spirit as the persistent compilation cache
   (utils/compile_cache.py).  Stale entries (other platform / device
   count / jax version / schema) are ignored with a
   ``tune_config_stale`` counter; missing entries fall back to the
   hand-picked constants byte-identically.
4. **Close with before/after rows** — one utilization-% row per tuned
   key, before and after, from the profiler's own attribution join.

Exit codes: 0 ok · 1 sweep/validation failure · 2 usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_OUT = os.path.join(REPO, "TUNE_TABLE.json")


def _parse_parameters(params):
    profile = {}
    for p in params:
        if "=" not in p:
            raise SystemExit(2)
        name, value = p.split("=", 1)
        profile[name] = value
    return profile


def _print_rows(title, rows, out):
    print(f"-- {title}", file=out)
    for r in rows:
        b, a = r.get("before", {}), r.get("after", {})
        bu, au = b.get("utilization_pct"), a.get("utilization_pct")
        print(f"   {r['name']:<36} "
              f"{b.get('engine') or b.get('config')} -> "
              f"{a.get('engine') or a.get('config')}  "
              f"util {bu if bu is not None else '-'}% -> "
              f"{au if au is not None else '-'}%  "
              f"(+{r.get('improvement_pct')}%)", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="autotune", description=__doc__.splitlines()[0])
    ap.add_argument("--analytic", action="store_true",
                    help="host-only analytic mode: the roofline cost "
                         "model, zero jax compiles (the CI smoke "
                         "gate)")
    ap.add_argument("--out", default=DEFAULT_OUT, metavar="FILE",
                    help=f"best-config table path (default "
                         f"{os.path.relpath(DEFAULT_OUT, REPO)})")
    ap.add_argument("--validate", action="store_true",
                    help="re-load + schema-validate the written table; "
                         "analytic mode additionally re-runs the sweep "
                         "and pins byte-identical output")
    ap.add_argument("--json", action="store_true", dest="json_out",
                    help="print the full sweep report as one JSON line")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed mode: min-of-N dispatches per candidate")
    ap.add_argument("--plugin", default="jerasure",
                    help="timed mode: plugin to tune")
    ap.add_argument("-P", "--parameter", action="append", default=[],
                    help="timed mode: profile parameter name=value")
    ap.add_argument("--size", type=int, default=1 << 18,
                    help="timed mode: object size per stripe")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--top", type=int, default=8,
                    help="baseline hot-program rows to print")
    args = ap.parse_args(argv)

    from ceph_tpu.tune import sweep as tsweep
    from ceph_tpu.tune.table import BestConfigTable, validate_table

    err = sys.stderr
    if args.analytic:
        report = tsweep.analytic_sweep(seed=args.seed)
        baseline = [r for r in report.attribution
                    if r.get("phase") == "before"][:args.top]
    else:
        try:
            import jax

            from ceph_tpu.telemetry.profiler import global_profiler

            jax.devices()  # fail fast on a dead backend
            # baseline: drive the engine's cached programs for the
            # chosen plugin so attribution_rows() has measured hot
            # rows BEFORE any tuning (the instrument's before side)
            from ceph_tpu.bench.erasure_code_benchmark import \
                ErasureCodeBench
            bench = ErasureCodeBench()
            bench.setup(["--plugin", args.plugin, "--size",
                         str(args.size), "--batch", str(args.batch),
                         "--workload", "profile", "--iterations", "2",
                         "-e", "1", "--seed", str(args.seed)]
                        + [x for p in args.parameter
                           for x in ("--parameter", p)])
            bench.run()
            prof = global_profiler()
            baseline = prof.attribution_rows()[:args.top]
        except Exception as e:  # noqa: BLE001 — report, fall back
            print(f"autotune: device unreachable "
                  f"({type(e).__name__}: {e}); use --analytic for "
                  f"the host-only sweep", file=err)
            return 1
        report = tsweep.timed_sweep(
            plugin=args.plugin,
            profile=_parse_parameters(args.parameter) or None,
            size=args.size, batch=args.batch, repeats=args.repeats,
            seed=args.seed)

    out = sys.stderr if args.json_out else sys.stdout
    print(f"autotune: mode={report.mode} platform={report.platform} "
          f"device_count={report.device_count} "
          f"candidates swept deterministically (seed {report.seed})",
          file=out)
    if baseline:
        print("-- baseline (attribution_rows, hottest first)",
              file=out)
        for r in baseline:
            print(f"   {r.get('series', r['name']):<64} "
                  f"util {r.get('utilization_pct')}% "
                  f"p50 {r.get('p50_ms')} ms", file=out)
    _print_rows("before/after (the tuner's own utilization rows)",
                report.rows, out)
    print(f"-- tuned keys: {len(report.table)}", file=out)
    for k in sorted(report.table.entries):
        print(f"   {k}: {report.table.entries[k]['config']}", file=out)

    errors = validate_table(report.table.to_dict())
    if errors:
        print(f"autotune: emitted table INVALID: {errors}", file=err)
        return 1
    try:
        report.table.save(args.out)
    except OSError as e:
        print(f"autotune: cannot write table to {args.out!r}: {e}",
              file=err)
        return 1
    print(f"autotune: best-config table -> {args.out} "
          f"(install via CEPH_TPU_TUNE_TABLE={args.out})", file=out)

    if args.validate:
        reloaded = BestConfigTable.load(args.out)
        if reloaded.to_json() != report.table.to_json():
            print("autotune: reloaded table differs from emitted",
                  file=err)
            return 1
        if args.analytic:
            again = tsweep.analytic_sweep(seed=args.seed)
            if json.dumps(again.to_dict(), sort_keys=True) != \
                    json.dumps(report.to_dict(), sort_keys=True):
                print("autotune: analytic sweep not deterministic",
                      file=err)
                return 1
        print("autotune: validation ok (schema + round-trip"
              + (" + determinism" if args.analytic else "") + ")",
              file=out)

    if args.json_out:
        print(json.dumps(report.to_dict(), sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
