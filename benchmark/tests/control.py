"""Readings the limits are set from, on the chip at the cell's own size:
for each seed, one set-up, one short window at the cell's own load, and
the comparison made twice -- with the plain reference (the program's
reading) and with the control (``controls.py``: the reference with one
stated guarantee broken).  All seeds run in one process, so the
programs compile once.

    python benchmark/tests/control.py --workload rs8_3.write \
        --seconds 10 --seeds 11 12 13

One JSON line per seed: {"seed", "program": {check: value},
"control": {check: value}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    from benchmark import harness
    from benchmark.tests import controls
    spec = harness.load_spec(args.workload)
    try:
        harness.require_devices("tpu", spec.chips)
    except harness.BenchError as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    from ceph_tpu.utils.compile_cache import initialize_compile_cache
    initialize_compile_cache()
    for seed in args.seeds:
        cell = harness.load_driver(spec).Cell(spec, seed)
        cell.setup()
        win = cell.window(args.seconds)
        cell.release()
        prog = {c.name: c.value for c in cell.check()}
        ctl = {c.name: c.value
               for c in cell.check(reference=controls.for_cell(cell))}
        print(json.dumps({"seed": seed, "workload": args.workload,
                          "window_s": win.seconds, "e2e": win.e2e,
                          "program": prog, "control": ctl}), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
