"""The per-layer readers of the program's stage spans and counters, on
hand-made window deltas: what each reads, and that each reads nothing
(None) from a program that records no such span or counter."""

import pytest

from benchmark import harness

SERVE = {
    "serve_admit_ms": "serve_admit_seconds",
    "serve_pack_ms": "serve_pack_seconds",
    "serve_device_wait_ms": "serve_device_wait_seconds",
    "serve_d2h_ms": "serve_d2h_seconds",
    "serve_demux_ms": "serve_demux_seconds",
}
CRUSH_TIME = {
    "crush_rung_ms_per_epoch": "crush_rung_seconds",
    "crush_host_residue_ms_per_epoch": "crush_host_residue_seconds",
    "crush_up_derive_ms_per_epoch": "crush_up_derive_seconds",
}


def ctx(after, before=None, **layer):
    deltas = harness.Deltas(before or {}, after)
    win = harness.Window(1.0, 1, 0, {}, layer)
    return harness.LayerContext(deltas, None, win, {}, {})


def read(name, c):
    return harness.load_reader(name).read(c)


# a window of 4 dispatches (3 full, 1 by deadline) after a warm-up
BEFORE = {"serve_dispatches{op=encode,reason=full}": 2,
          "serve_pack_seconds{op=encode}": (2, 0.5)}
AFTER = {"serve_dispatches{op=encode,reason=full}": 5,
         "serve_dispatches{op=encode,reason=deadline}": 1,
         "serve_admit_seconds": (9, 0.004),
         "serve_pack_seconds{op=encode}": (6, 0.58),
         "serve_device_wait_seconds{op=encode}": (4, 0.002),
         "serve_d2h_seconds{op=encode}": (4, 0.02),
         "serve_demux_seconds{op=encode}": (4, 0.0008),
         "serve_batch_wait_seconds{op=encode}": (200, 5.0)}


@pytest.mark.parametrize("name", sorted(SERVE))
def test_serve_stage_ms_per_dispatch(name):
    c = ctx(AFTER, BEFORE)
    count, total = c.telemetry.hist(SERVE[name])
    assert read(name, c) == pytest.approx(1e3 * total / 4)
    assert read(name, ctx({"serve_dispatches{op=encode}": 4})) is None


def test_serve_pack_reads_the_window_delta():
    assert read("serve_pack_ms", ctx(AFTER, BEFORE)) \
        == pytest.approx(1e3 * 0.08 / 4)


def test_deadline_fire_pct_and_batch_wait():
    c = ctx(AFTER, BEFORE)
    assert read("serve_deadline_fire_pct", c) == pytest.approx(25.0)
    assert read("serve_batch_wait_ms", c) == pytest.approx(25.0)
    parent = ctx({"serve_dispatches{op=encode}": 4})
    assert read("serve_deadline_fire_pct", parent) is None
    assert read("serve_batch_wait_ms", parent) is None


CRUSH = {"crush_rung_seconds{rung=0}": (16, 6.4),
         "crush_rung_seconds{rung=1}": (4, 0.8),
         "crush_redo_lanes": 1200,
         "crush_host_residue_seconds": (16, 12.0),
         "crush_host_residue_lanes": 800,
         "crush_up_derive_seconds": (16, 3.2)}


@pytest.mark.parametrize("name", sorted(CRUSH_TIME))
def test_crush_ms_per_epoch(name):
    c = ctx(CRUSH, epochs=16)
    _, total = c.telemetry.hist(CRUSH_TIME[name])
    assert read(name, c) == pytest.approx(1e3 * total / 16)
    assert read(name, ctx({}, epochs=16)) is None
    assert read(name, ctx(CRUSH)) is None


def test_crush_lanes_per_epoch():
    c = ctx(CRUSH, epochs=16)
    assert read("crush_redo_lanes_per_epoch", c) == 75.0
    assert read("crush_residue_lanes_per_epoch", c) == 50.0
    # instrumented, but no deeper rung ran: 0, not missing
    quiet = {k: v for k, v in CRUSH.items() if k != "crush_redo_lanes"}
    assert read("crush_redo_lanes_per_epoch", ctx(quiet, epochs=16)) == 0
    for name in ("crush_redo_lanes_per_epoch",
                 "crush_residue_lanes_per_epoch"):
        assert read(name, ctx({}, epochs=16)) is None


def test_every_new_metric_has_a_reader_and_its_cells():
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in list(SERVE) + ["serve_deadline_fire_pct",
                               "serve_batch_wait_ms"]:
        assert per_layer[name]["workloads"] == ["rs8_3.write",
                                                "clay8_4_11.recover"]
        assert per_layer[name]["moves"] == "ec_gbps"
    for name in list(CRUSH_TIME) + ["crush_redo_lanes_per_epoch",
                                    "crush_residue_lanes_per_epoch"]:
        assert per_layer[name]["workloads"] == ["rs8_3.osd_out_remap"]
        assert per_layer[name]["moves"] == "placement_pgs_per_s"
        assert per_layer[name]["layer"] == "bulk CRUSH"
