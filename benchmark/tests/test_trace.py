"""The trace reduction: exact arithmetic on a hand-made trace, and a
small trace recorded on a TPU v5e (``record_trace.py``) read end to
end."""

import glob
import os
from collections import namedtuple

import pytest

from benchmark import peaks, trace

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")

DATA = os.path.join(os.path.dirname(__file__), "data")


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Event("bench.window", 1000, 10000),
        Event("bench.submit", 1000, 2000),
        Event("bench.poll", 3000, 4000),
        Event("bench.collect", 7000, 4000),
        Event("other", 0, 50000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Event("jit_raw(123)", 3500, 2000),
                             Event("jit_raw(456)", 6000, 500),
                             Event("jit_fn(9)", 500, 1000)]),
        Line("XLA Ops", [Event("%fusion.3 = u8[4] fusion(x)", 3500, 1000),
                         Event("%apply_matrix_pallas.1 = u8[4] custom-call",
                               4000, 1500),
                         Event("%copy.2 = u8[4] copy(y)", 6000, 500),
                         Event("%early = u8[1] copy(z)", 500, 1000)])])
    return [host, dev]


def test_reduction_arithmetic():
    s = trace.reduce_planes(_planes())
    # window 1000..11000; busy = [1000,1500) + [3500,5500) + [6000,6500)
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == pytest.approx(3000e-9)
    assert s.idle_pct == pytest.approx(70.0)
    assert s.devices == 1
    # module time clipped to the window: jit_fn ran 500..1500
    assert s.programs["jit_raw"] == pytest.approx(2500e-9)
    assert s.programs["jit_fn"] == pytest.approx(500e-9)
    assert s.program_runs == {"jit_raw": 2, "jit_fn": 1}
    assert s.program_seconds(("jit_raw",)) == (pytest.approx(2500e-9), 2)
    ops = dict(s.ops)
    assert ops["apply_matrix_pallas"] == pytest.approx(1500e-9)
    assert ops["fusion"] == pytest.approx(1000e-9)
    assert ops["copy"] == pytest.approx(500e-9)
    # gaps: [1500,3500) under submit+poll (poll covers 500 of it,
    # submit 1500), [5500,6000) under poll, [6500,11000) under collect
    assert s.gaps[0] == ("bench.collect", pytest.approx(4500e-9))
    assert s.gaps[1] == ("bench.submit", pytest.approx(2000e-9))
    assert s.gaps[2] == ("bench.poll", pytest.approx(500e-9))


def test_reduction_needs_a_window_and_a_device():
    host, dev = _planes()
    with pytest.raises(ValueError):
        trace.reduce_planes([dev])
    with pytest.raises(ValueError):
        trace.reduce_planes([host])


def test_names():
    assert trace.program_name("jit_raw(14309613001335494339)") == "jit_raw"
    assert trace.op_name("%copy_bitcast_fusion = u8[4,8] fusion(...)") \
        == "copy_bitcast_fusion"
    assert trace.op_name("%apply_matrix_pallas.1 = u8[4]") \
        == "apply_matrix_pallas"
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_recorded_tpu_trace():
    (path,) = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    s = trace.reduce_file(path)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_pct < 100
    # one RS encode and one clay decode dispatch, one CRUSH sweep
    assert s.program_runs == {"jit_raw": 2, "jit_fn": 1}
    assert s.programs["jit_fn"] > s.programs["jit_raw"] > 0
    names = {n for n, _ in s.ops}
    assert "apply_matrix_pallas" in names or "fusion" in names
    assert all(n.startswith("bench.") or n == "untracked"
               for n, _ in s.gaps)
    assert sum(g for _, g in s.gaps) <= s.window_s - s.busy_s + 1e-9


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
