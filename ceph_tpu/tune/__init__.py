"""ceph_tpu.tune — the roofline-closing autotuner (ISSUE 14).

Profiler-driven config search over a bounded declarative space
(tune/space.py), persisting winners in a versioned, schema-validated
best-config table (tune/table.py) the engine's consultation seams
read at program-build time.  Two measurement modes (tune/sweep.py):
host-only analytic (zero compiles — the ``tune.sweep`` audit entry)
and timed min-of-N eager dispatch.
docs/PERF.md "Roofline-closing autotuner" has the full story;
tools/autotune.py is the CLI.
"""

from .table import (BestConfigTable, active_source, active_table,
                    consult, install_table, key_hash, key_str,
                    matrix_digest, profile_str, scoped_table,
                    tuning_key, validate_table)

__all__ = [
    "BestConfigTable", "active_source", "active_table", "consult",
    "install_table", "key_hash", "key_str", "matrix_digest",
    "profile_str", "scoped_table", "tuning_key", "validate_table",
]
