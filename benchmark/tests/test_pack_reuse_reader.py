"""The ``serve_pack_reuse_pct`` reader on hand-made window deltas: what it
reads, that it reads nothing (None) from a program that counts no
staging buffers, and its entry in BENCHMARK.json."""

import pytest

from benchmark import harness


def ctx(after, before=None):
    deltas = harness.Deltas(before or {}, after)
    win = harness.Window(1.0, 1, 0, {}, {})
    return harness.LayerContext(deltas, None, win, {}, {})


def read(c):
    return harness.load_reader("serve_pack_reuse_pct").read(c)


def test_pack_reuse_pct_reads_the_window_delta():
    """Reused over all staging-buffer acquisitions in the window: the
    warm-up's allocations fall before it."""
    before = {"serve_pack_buffers{op=decode,outcome=allocated}": 1,
              "serve_pack_buffers{op=decode,outcome=reused}": 11}
    after = {"serve_pack_buffers{op=decode,outcome=allocated}": 1,
             "serve_pack_buffers{op=decode,outcome=reused}": 41}
    assert read(ctx(after, before)) == 100.0
    after["serve_pack_buffers{op=encode,outcome=allocated}"] = 2
    after["serve_pack_buffers{op=encode,outcome=reused}"] = 8
    assert read(ctx(after, before)) == pytest.approx(100.0 * 38 / 40)


def test_pack_reuse_pct_is_none_without_the_counter():
    parent = {"serve_dispatches{op=encode,reason=full}": 5,
              "serve_pack_seconds{op=encode}": (6, 0.58)}
    assert read(ctx(parent, {})) is None
    idle = {"serve_pack_buffers{op=decode,outcome=reused}": 3}
    assert read(ctx(idle, idle)) is None


def test_pack_reuse_pct_entry():
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}["serve_pack_reuse_pct"]
    assert entry == {"name": "serve_pack_reuse_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "serve", "moves": "ec_gbps",
                     "workloads": ["rs8_3.write", "clay8_4_11.recover"]}
    assert bench["per_layer"][-1] is entry
