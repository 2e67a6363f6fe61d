"""CPU rehearsal of the benchmark at tiny sizes (never a device number).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

SEED = 2**31 + 17   # run seeds may need more than 32 signed bits


def tiny(spec):
    """The same cell at a size a CPU test can hold: 32 KiB stripes,
    2 objects in flight over 3, a 64-OSD map of 256 PGs."""
    spec.config = copy.deepcopy(spec.config)
    spec.traffic = copy.deepcopy(spec.traffic)
    if spec.traffic["driver"] == "ec_serve":
        spec.config["stripe_width"] = 8 * 64 * 64
        spec.traffic.update(object_bytes=4 * 8 * 64 * 64,
                            outstanding_objects=2, dataset_objects=3,
                            check_every=1, check_ops=6)
    else:
        spec.config["cluster"].update(racks=4, hosts_per_rack=4,
                                      osds_per_host=4)
        spec.config["pool"]["pg_num"] = 256
    return spec


@pytest.fixture
def tiny_spec():
    from benchmark import harness

    def make(workload):
        return tiny(harness.load_spec(workload))
    return make


@pytest.fixture(scope="session")
def cpu():
    import jax
    return jax.devices("cpu")[:1]
