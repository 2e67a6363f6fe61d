"""One run of one cell, as data: find the cell's files by name, set up,
measure the window, check the answers, read the per-layer metrics.

Everything a cell owns is found by the names ``BENCHMARK.json`` gives:

- ``configs[].file``                   the deployment (JSON);
- ``benchmark/traffic/<traffic>.json``  the traffic mix (JSON); its
  ``driver`` names the general generator in ``benchmark/drivers/``;
- ``benchmark/metrics/<metric>.py``     one per-layer reader each;
- ``benchmark/reference/<plugin>.py``   the plain reference of a code.

A driver module exposes ``Cell(spec, seed)`` with ``setup()``,
``window(seconds) -> Window``, ``release()``, ``check(reference=None)
-> [Check]``; it records host spans named ``bench.*`` around its calls
into the program.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")


class BenchError(RuntimeError):
    """The run cannot produce a result."""


@dataclass
class Check:
    """One number compared with the plain reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Window:
    """What a driver measured in its window."""
    seconds: float
    attempted: int
    failed: int
    e2e: Dict[str, float]
    # inputs of the per-layer readers (counts made by the benchmark)
    layer: Dict[str, float] = field(default_factory=dict)


@dataclass
class Spec:
    workload: dict
    config: dict
    traffic: dict
    bench: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: str = ROOT) -> Spec:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return Spec(cell, config, traffic, bench)


def applies(metric: dict, workload: str, e2e_names=()) -> bool:
    """Whether ``metric`` is reported in ``workload``: listed there, or
    (with no list) wherever the end-to-end metric it moves is."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def stage(name: str, t0: float) -> None:
    """Log how far into set-up a stage ended (standard error)."""
    print(f"set-up: {name} at {time.perf_counter() - t0:.3f} s",
          file=sys.stderr, flush=True)


# -- telemetry deltas ----------------------------------------------------

def snapshot() -> dict:
    from ceph_tpu.telemetry import global_metrics
    (body,) = global_metrics().dump().values()
    out = {}
    for series, v in body.items():
        if isinstance(v, dict) and "count" in v and "sum" in v:
            out[series] = (v["count"], v["sum"])
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[series] = v
    return out


class Deltas:
    """Counter and histogram changes between two snapshots."""

    def __init__(self, before: dict, after: dict) -> None:
        self.d = {}
        for k, v in after.items():
            b = before.get(k)
            if isinstance(v, tuple):
                b = b or (0, 0.0)
                self.d[k] = (v[0] - b[0], v[1] - b[1])
            else:
                self.d[k] = v - (b or 0)

    def _series(self, name: str):
        for k, v in self.d.items():
            if k == name or k.startswith(name + "{"):
                yield k, v

    def total(self, name: str) -> float:
        return sum(v for _, v in self._series(name)
                   if not isinstance(v, tuple))

    def hist(self, name: str):
        """(count, sum) over every labelled series of a histogram."""
        c = s = 0
        for _, v in self._series(name):
            if isinstance(v, tuple):
                c += v[0]
                s += v[1]
        return c, s


@dataclass
class LayerContext:
    """What a per-layer reader gets."""
    telemetry: Deltas
    trace: object            # trace.TraceSummary
    window: Window
    device: dict
    peaks: dict


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_name = "benchmark.metrics." + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise BenchError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(spec: Spec):
    return importlib.import_module("benchmark.drivers."
                                   + spec.traffic["driver"])


# -- devices -------------------------------------------------------------

def require_devices(platform: str, count: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < count:
        raise BenchError(
            f"need {count} {platform} device(s); jax reports "
            f"{len(devs)} {devs[0].platform} ({devs[0].device_kind})")
    return devs[:count]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- one run ---------------------------------------------------------------

def run(spec: Spec, seed: int, seconds: float, trace: bool, t0: float,
        devs, log=None) -> dict:
    """Set up, measure, check; return the result line's object."""
    from ceph_tpu.telemetry import install_compile_monitor
    from . import peaks as peak_table
    from . import trace as trace_mod

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    install_compile_monitor()
    cell = load_driver(spec).Cell(spec, seed)
    cell.setup()
    # Set-up leaves millions of tracked objects and a full collection
    # made due by its own allocations; run that collection here, so
    # that it is not a second-long stall at a random point of the window.
    gc.collect()
    setup_s = time.perf_counter() - t0
    full_gc = gc.get_stats()[2]["collections"]
    before = snapshot()
    if trace:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with span("bench.window"):
            win = cell.window(seconds)
    finally:
        if trace:
            import jax
            jax.profiler.stop_trace()
    deltas = Deltas(before, snapshot())
    compiles = int(deltas.total("jax_backend_compiles"))
    misses = int(deltas.total("jax_persistent_cache_misses"))
    full_gc = gc.get_stats()[2]["collections"] - full_gc
    log(f"window: {win.seconds:.3f} s, compiles {compiles}, "
        f"persistent-cache misses {misses}, full collections {full_gc}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    cell.release()
    checks = cell.check()
    result = {"correct": all(c.ok for c in checks),
              "attempted": int(win.attempted), "failed": int(win.failed)}
    e2e = [m for m in spec.bench["end_to_end"] if applies(m, spec.name)]
    if not trace:
        vals = dict(win.e2e, setup_s=setup_s)
        metrics = {}
        for m in e2e:
            if m["name"] not in vals:
                raise BenchError(f"{spec.name} measured no {m['name']}")
            metrics[m["name"]] = {"value": vals[m["name"]],
                                  "unit": m["unit"]}
    else:
        summary = trace_mod.reduce_file(trace_mod.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = LayerContext(deltas, summary, win, dev,
                           peak_table.peaks(dev["kind"]))
        names = [m["name"] for m in e2e]
        metrics = {}
        for m in spec.bench["per_layer"]:
            if not applies(m, spec.name, names):
                continue
            v = load_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps]}
    result["metrics"] = metrics
    result["device"] = dev
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def check_lines(result: dict) -> List[str]:
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in result["checks"].items()]
