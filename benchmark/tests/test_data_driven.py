"""A configuration, a traffic mix and a per-layer metric are found by
name: added as new files plus new BENCHMARK.json entries in a copy of
the benchmark, with no existing file edited."""

import json
import os
import shutil
import subprocess
import sys

from benchmark.tests.conftest import ROOT

SCRIPT = r'''
import json, sys, time, types
sys.path.insert(0, {copy!r})
sys.path.append({root!r})
from benchmark import harness
from benchmark.tests.conftest import tiny
assert harness.HERE.startswith({copy!r}), harness.HERE
spec = tiny(harness.load_spec("rs4_2.write_small"))
assert spec.config["profile"]["k"] == 4 and spec.traffic["check_ops"] == 6
import jax
res = harness.run(spec, 5, 0.5, False, time.perf_counter(),
                  jax.devices()[:1], log=lambda m: None)
assert res["correct"] and "ec_gbps" in res["metrics"], res
reader = harness.load_reader("test_stripes_per_op")
names = [m["name"] for m in spec.bench["per_layer"]
         if harness.applies(m, spec.name, ["ec_gbps"])]
assert "test_stripes_per_op" in names
win = harness.Window(1.0, 1, 0, {{}}, {{"stripes": 8, "ops": 2}})
ctx = harness.LayerContext(None, None, win, {{}}, {{}})
assert reader.read(ctx) == 4.0
print("ok")
'''


def test_new_cell_by_new_files_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    before = {p: open(p, "rb").read()
              for p in map(str, copy.rglob("*")) if os.path.isfile(p)}
    b = copy / "benchmark"
    cfg = json.load(open(b / "configs" / "rs8_3_host_1k.json"))
    cfg.update(name="rs4_2_host", profile={"technique": "reed_sol_van",
                                           "k": 4, "m": 2})
    (b / "configs" / "rs4_2_host.json").write_text(json.dumps(cfg))
    tr = json.load(open(b / "traffic" / "write.json"))
    tr["object_bytes"] = 1 << 22
    (b / "traffic" / "write_small.json").write_text(json.dumps(tr))
    (b / "metrics" / "test_stripes_per_op.py").write_text(
        "def read(ctx):\n"
        "    ops = ctx.window.layer.get('ops')\n"
        "    return ctx.window.layer['stripes'] / ops if ops else None\n")
    bench = json.load(open(copy / "BENCHMARK.json"))
    bench["configs"].append({"name": "rs4_2_host", "source": "test",
                             "file": "benchmark/configs/rs4_2_host.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "rs4_2.write_small",
                               "config": "rs4_2_host",
                               "traffic": "write_small", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "rs8_3.write" in m["workloads"]:
            m["workloads"].append("rs4_2.write_small")
    bench["per_layer"].append({"name": "test_stripes_per_op",
                               "unit": "stripes", "better": "higher",
                               "source": "program_counter", "layer": "serve",
                               "moves": "ec_gbps"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c",
                        SCRIPT.format(copy=str(copy), root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), p.stderr
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path, "rb").read() == content, path
