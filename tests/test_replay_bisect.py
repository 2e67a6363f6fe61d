"""tools/replay_bisect.py (ISSUE 20): the divergence witness.

- digest-chain mechanics: cumulative, so divergence is monotone and
  the binary search is valid (identical → None; payload divergence →
  exact first index; length mismatch → the boundary);
- the pinned acceptance criterion: injecting ONE service-time jitter
  through the serve.batcher seam on run B is localized to the exact
  first dispatch whose batch composition changed — a dispatch-level
  checkpoint, well before the aggregate report fragments;
- two clean runs of the same seeded week replay byte-identically
  (the in-process determinism gate the CLI's default mode wraps).
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

from replay_bisect import (  # noqa: E402
    _deterministic_jitter,
    checkpoint_stream,
    digest_chain,
    first_divergence,
    run_week_stream,
)

from ceph_tpu.scenario.spec import tenant_week_scenario  # noqa: E402

TINY = dict(seed=17, days=1, day_s=6.0,
            peak_rates=(40.0, 30.0, 20.0), burst_factor=80.0)


# ----------------------------------------------------------------------
# chain mechanics (no scenario runs)

def _stream(*payloads):
    return [(f"cp[{i}]", p) for i, p in enumerate(payloads)]


def test_identical_streams_no_divergence():
    s = _stream("a", "b", "c")
    assert first_divergence(s, list(s)) is None


def test_payload_divergence_pinned_to_first_index():
    a = _stream("a", "b", "c", "d", "e")
    b = _stream("a", "b", "X", "d", "e")
    d = first_divergence(a, b)
    assert d["index"] == 2 and d["kind"] == "payload"
    assert d["payload_a"] == "c" and d["payload_b"] == "X"
    # everything AFTER the divergence differs too (cumulative chain)
    # yet the search still names the first
    assert digest_chain(a)[3] != digest_chain(b)[3]


def test_length_mismatch_is_the_divergence():
    a = _stream("a", "b")
    b = _stream("a", "b", "extra")
    d = first_divergence(a, b)
    assert d["kind"] == "length" and d["index"] == 2
    assert d["extra_checkpoints"] == 1
    assert d["payload_b"] == "extra" and d["payload_a"] is None


def test_chain_is_cumulative():
    a = digest_chain(_stream("a", "b"))
    b = digest_chain(_stream("X", "b"))
    # same payload at index 1, but the chains differ there because
    # index 0 differed — that prefix-folding is what makes "first
    # divergent checkpoint" monotone
    assert a[1] != b[1]


# ----------------------------------------------------------------------
# the pinned acceptance criterion (one scenario, run three times)

@pytest.fixture(scope="module")
def streams():
    clean_a = run_week_stream(tenant_week_scenario(**TINY))
    clean_b = run_week_stream(tenant_week_scenario(**TINY))
    jittered = run_week_stream(tenant_week_scenario(**TINY),
                               jitter=_deterministic_jitter)
    return clean_a, clean_b, jittered


def test_clean_reruns_are_byte_identical(streams):
    clean_a, clean_b, _ = streams
    assert first_divergence(clean_a, clean_b) is None


def test_injected_jitter_localized_to_exact_checkpoint(streams):
    clean_a, _, jittered = streams
    d = first_divergence(clean_a, jittered)
    assert d is not None, "injected jitter produced no divergence"
    # the EWMA perturbation at dispatch 8 first becomes OBSERVABLE at
    # dispatch 22 — the first batch whose composition changed (a
    # linear walk of the two streams finds the same index; the week's
    # decodes share one pattern-generic bucket per erasure count) —
    # and the witness walks it back there, not to the aggregate report
    assert d["kind"] == "payload"
    assert d["index"] == 22, d
    assert d["index"] == next(i for i, (x, y) in
                              enumerate(zip(clean_a, jittered)) if x != y)
    assert d["label_a"].startswith("dispatch[00022]"), d["label_a"]
    # log2(checkpoints) probes, not a linear walk
    assert d["probes"] <= 10


def test_checkpoint_stream_shape(streams):
    clean_a, _, _ = streams
    labels = [lbl for lbl, _ in clean_a]
    assert labels[0].startswith("dispatch[00000]")
    assert "qos.arbiter" in labels
    assert "recovery.counters" in labels
    assert any(lbl == "report.slo" for lbl in labels)
    assert any(lbl == "report.tenants" for lbl in labels)
    # dispatch checkpoints come first, in dispatch order
    dispatch = [lbl for lbl in labels if lbl.startswith("dispatch[")]
    assert dispatch == sorted(dispatch)
    assert labels[:len(dispatch)] == dispatch
