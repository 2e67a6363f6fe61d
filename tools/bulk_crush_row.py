#!/usr/bin/env python3
"""BASELINE.md row 5: 1M-PG bulk CRUSH sweep on the live device.

Prints one JSON line; invoked by tools/bench_rows.sh (which records it
in chiprun_out/bench_rows.jsonl with provenance).  --ec sweeps the
canonical mon-generated erasure rule (SET steps + chooseleaf indep 0,
6-wide) instead of the replicated firstn rule.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ceph_tpu.crush import bulk
from ceph_tpu.crush.builder import CrushBuilder


def main() -> int:
    ec = "--ec" in sys.argv[1:]
    b = CrushBuilder()
    root = b.build_two_level(8, 4)
    if ec:
        from ceph_tpu.crush.types import (step_chooseleaf_indep,
                                          step_emit,
                                          step_set_choose_tries,
                                          step_set_chooseleaf_tries,
                                          step_take)
        b.add_rule(0, [step_set_chooseleaf_tries(5),
                       step_set_choose_tries(100), step_take(root),
                       step_chooseleaf_indep(0, b.type_id("host")),
                       step_emit()])
        nrep = 6
    else:
        b.add_simple_rule(0, root, "host", firstn=True)
        nrep = 3
    xs = np.arange(1_000_000)
    # one CompiledCrushMap reused so the jit cache persists, warmed at
    # the FULL sweep shape (jit specializes on shape) — the timed call
    # then measures throughput, not compilation
    cm = bulk.CompiledCrushMap(b.map)
    bulk.bulk_do_rule(cm, 0, xs, nrep)
    t0 = time.perf_counter()
    bulk.bulk_do_rule(cm, 0, xs, nrep)
    dt = time.perf_counter() - t0
    metric = ("bulk_crush_ec_rule_mappings_per_s" if ec
              else "bulk_crush_mappings_per_s")
    print(json.dumps({"metric": metric,
                      "value": round(len(xs) / dt), "unit": "mappings/s",
                      "n": len(xs), "seconds": round(dt, 3)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
