"""The reduction from trace to metrics, on a small trace recorded on a
TPU v5e (``data/fixture.xplane.pb``: three solves of one small jitted
program, 20 ms host sleeps labelled ``make_data`` between them) and on
synthetic intervals."""
import pathlib

import numpy as np
import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "fixture.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert trace._union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [[0, 4], [5, 9]]
    assert trace._union([]) == []


def test_label_prefers_cover_then_the_innermost_span():
    spans = [("solve", 0, 100), ("sync", 40, 100), ("make_data", 100, 120)]
    assert trace._label(spans, 50, 90) == "sync"  # inside solve and sync
    assert trace._label(spans, 10, 30) == "solve"
    assert trace._label(spans, 95, 119) == "make_data"  # 19 ns of it, 5 of sync
    assert trace._label(spans, 130, 140) == "host"


def _raw(path):
    """Device op intervals and host spans, read without bench.trace."""
    from jax.profiler import ProfileData

    ops, spans = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/device:TPU:") and line.name == "XLA Ops":
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                elif plane.name.startswith("/host:") and ev.name in trace.SPANS:
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, spans


@pytest.fixture(scope="module")
def summary():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace")
    return trace.summarize(str(FIXTURE))


def test_fixture_busy_is_the_union_of_device_ops(summary):
    ops, spans = _raw(FIXTURE)
    (w0, w1), = [(s, e) for n, s, e in spans if n == "window"]
    # a 1 us timeline: busy where any op runs, the window's length
    t0 = int(w0 // 1000)
    busy = np.zeros(int(w1 // 1000) - t0 + 1, bool)
    for s, e, _ in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            busy[int(s // 1000) - t0:int(np.ceil(e / 1000)) - t0] = True
    assert summary.window_ns == pytest.approx(w1 - w0)
    assert summary.busy_ns == pytest.approx(busy.sum() * 1000, rel=0.02, abs=5000)
    assert 0.0 < summary.idle_share < 1.0


def test_fixture_device_time_per_name(summary):
    ops, spans = _raw(FIXTURE)
    (w0, w1), = [(s, e) for n, s, e in spans if n == "window"]
    total = sum(min(e, w1) - max(s, w0) for s, e, name in ops
                if min(e, w1) > max(s, w0) and not name.lstrip("%").startswith(trace.CONTAINERS))
    assert sum(summary.device_ns.values()) == pytest.approx(total)
    top = summary.top_ops(10)
    assert top == sorted(top, key=lambda kv: -kv[1])
    assert summary.device_ns_matching("") == pytest.approx(total)


def test_fixture_idle_gaps_are_labelled_by_span(summary):
    labels = [label for label, _ in summary.top_gaps(3)]
    # the three longest gaps are the 20 ms host sleeps between solves
    assert labels == ["make_data"] * 3
    assert all(s >= 0.019 for _, s in summary.top_gaps(3))
    assert summary.spans == {"make_data": 3, "solve": 3, "sync": 3}
    assert sum(ns for _, ns in summary.gaps) == pytest.approx(summary.window_ns - summary.busy_ns)
