"""The benchmark's command: one run of one cell on the chip(s) it names.

    python benchmark/run.py --workload rs8_3.write --seed 7 --seconds 30 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and,
with ``--trace 1``, ``breakdown``), and ``checks`` last: every number
compared with the plain reference beside its limit.  The same checks
are the last lines of standard error.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  JAX's compilation cache is kept at the fixed
path ``<checkout>/.jax_cache``, whatever the environment says.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# import the benchmark as a package from the checkout root, so that its
# module names never shadow the standard library's
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    spec = harness.load_spec(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    try:
        devs = harness.require_devices("tpu", spec.chips)
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    from ceph_tpu.utils.compile_cache import initialize_compile_cache
    initialize_compile_cache()
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         T0, devs)
    print(json.dumps(result), flush=True)
    print("\n".join(harness.check_lines(result)), file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
