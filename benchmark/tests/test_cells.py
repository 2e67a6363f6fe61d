"""Every cell, end to end on the CPU at a tiny size: set-up, window,
comparison with the plain reference; its control and its planted
faults must come out as not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import controls
from benchmark.tests.conftest import ROOT, SEED

CELLS = ["rs8_3.write", "clay8_4_11.recover", "rs8_3.osd_out_remap"]


def run_cell(spec, cpu, seconds=1.0):
    import time
    return harness.run(spec, SEED, seconds, False, time.perf_counter(), cpu,
                       log=lambda msg: None)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(workload, tiny_spec, cpu):
    spec = tiny_spec(workload)
    res = run_cell(spec, cpu)
    assert res["correct"], res["checks"]
    want = {m["name"] for m in spec.bench["end_to_end"]
            if harness.applies(m, workload)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, tiny_spec, cpu):
    spec = tiny_spec(workload)
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    cell.window(1.0)
    cell.release()
    assert all(c.ok for c in cell.check())
    bad = cell.check(reference=controls.for_cell(cell))
    assert not all(c.ok for c in bad), bad


def _flip(stack, out):
    out = np.array(out)
    out.reshape(-1)[0] ^= 1
    return out


def _half(stack, out):
    """The second half of the batch's requests left out (the rung's
    zero padding rows do not count)."""
    out = np.array(out)
    real = int(np.asarray(stack).reshape(len(out), -1).any(axis=1).sum())
    out[real // 2:real] = 0
    return out


@pytest.mark.parametrize("fault", [_flip, _half], ids=["altered", "half"])
@pytest.mark.parametrize("workload", CELLS[:2])
def test_ec_fault_is_not_correct(workload, fault, tiny_spec, cpu,
                                 monkeypatch):
    from ceph_tpu.codes import engine
    real = engine.serve_dispatch_call

    def broken(*args, **kwargs):
        call = real(*args, **kwargs)
        return lambda stack: fault(stack, call(stack))
    spec = tiny_spec(workload)
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    monkeypatch.setattr(engine, "serve_dispatch_call", broken)
    cell.window(1.0)
    cell.release()
    assert not all(c.ok for c in cell.check())


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_placement_fault_is_not_correct(fault, tiny_spec, cpu, monkeypatch):
    from ceph_tpu.crush.osdmap import OSDMap
    real = OSDMap.pg_to_up_bulk
    last = {}

    def broken(self, *args, **kwargs):
        up, prim = real(self, *args, **kwargs)
        up, prim = np.array(up), np.array(prim)
        if fault == "altered":
            up[0, 0] = (up[0, 0] + 1) % self.max_osd
        elif "up" in last:
            # half of the PGs left as the previous epoch placed them
            h = len(up) // 2
            up[h:], prim[h:] = last["up"][h:], last["prim"][h:]
        last["up"], last["prim"] = up.copy(), prim.copy()
        return up, prim
    spec = tiny_spec("rs8_3.osd_out_remap")
    cell = harness.load_driver(spec).Cell(spec, SEED)
    cell.setup()
    monkeypatch.setattr(OSDMap, "pg_to_up_bulk", broken)
    cell.window(1.0)
    cell.release()
    assert not all(c.ok for c in cell.check())


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "rs8_3.write", "--seed", str(SEED), "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_run_exits_nonzero_without_the_program(tmp_path):
    import shutil
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "rs8_3.write", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_json_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert hasattr(harness.load_driver(harness.load_spec(w["name"])),
                       "Cell")
    for m in bench["per_layer"]:
        assert hasattr(harness.load_reader(m["name"]), "read")
