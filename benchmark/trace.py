"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  It
is read with ``jax.profiler.ProfileData`` and nothing else.  What is
taken from it, all clipped to the benchmark's own ``bench.window``
host span:

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), and
  the idle share 1 - busy / window, averaged over the devices;
- per-program device time: the ``XLA Modules`` events, keyed by the
  program's name without its fingerprint (``jit_raw(1234)`` ->
  ``jit_raw``), summed over the devices;
- the device operations that took the most time, keyed by the HLO
  instruction's name without its numeric suffix;
- the longest idle gaps of the first device, each named by the
  benchmark host span (``bench.*``, other than the window) that
  overlaps it most, or ``untracked`` where none does.

Host spans and device events share the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10

_FINGERPRINT = re.compile(r"\(\d+\)$")
_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")


@dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                      # mean over devices
    programs: Dict[str, float] = field(default_factory=dict)
    program_runs: Dict[str, int] = field(default_factory=dict)
    ops: List[Tuple[str, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_seconds(self, prefixes) -> Tuple[float, int]:
        """Device seconds and runs of the programs whose name starts
        with one of ``prefixes``."""
        secs = sum(v for k, v in self.programs.items()
                   if k.startswith(tuple(prefixes)))
        runs = sum(v for k, v in self.program_runs.items()
                   if k.startswith(tuple(prefixes)))
        return secs, runs


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce_planes(planes, top: int = TOP) -> TraceSummary:
    """Reduce ``ProfileData.planes`` (or objects shaped alike)."""
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, e))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    if not devices:
        raise ValueError(f"trace holds no {DEVICE_PREFIX}* plane")
    devices.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):] or 0))
    programs: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    for i, plane in enumerate(devices):
        busy = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for name, s, e in _events(line):
                    c = _clip(s, e, lo, hi)
                    if c:
                        busy.append(c)
                        key = op_name(name)
                        ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
            elif line.name == MODULES_LINE:
                for name, s, e in _events(line):
                    c = _clip(s, e, lo, hi)
                    if c:
                        key = program_name(name)
                        programs[key] = (programs.get(key, 0.0)
                                         + (c[1] - c[0]) * 1e-9)
                        runs[key] = runs.get(key, 0) + 1
        merged = merge(busy)
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            first_busy = merged
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    gaps = []
    edge = lo
    for s, e in first_busy + [(hi, hi)]:
        if s > edge:
            gaps.append((_name_gap(host, edge, s), (s - edge) * 1e-9))
        edge = max(edge, e)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, devices=len(devices),
        busy_s=busy_total * 1e-9 / len(devices), programs=programs,
        program_runs=runs,
        ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        gaps=gaps[:top])


def _name_gap(host, s: float, e: float) -> str:
    best, name, best_len = 0.0, "untracked", float("inf")
    for n, hs, he in host:
        ov = min(e, he) - max(s, hs)
        # an inner span that covers as much wins over its parent
        if ov > best or (ov == best and ov > 0 and he - hs < best_len):
            best, name, best_len = ov, n, he - hs
    return name


def reduce_file(path: str, top: int = TOP) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top)
