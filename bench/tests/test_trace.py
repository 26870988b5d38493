"""The reduction from trace to metrics (``bench/trace.py``), on two traces
recorded on a TPU v5e and on synthetic intervals.

``data/fixture.xplane.pb``: three solves of one small anonymous jit,
20 ms host sleeps labelled ``make_data`` between them.

``data/stages.xplane.pb``: a traced run of the eps-k6 cell at 2,048
points, ``harness.run_cell("simjoin-syn3d.eps-k6", 2147483661, 0.02,
True, overrides={"config": {"n_points": 2048}})`` with the file kept.
Its window holds one join with the program's spans and stage modules.
Host threads that held only compiler passes and transfers were dropped
from it to keep it small; the device plane and the Python, main and
task threads are as recorded.  In it the device's clock runs about
1.2 ms ahead of the host's, so the join's first two modules
(``reorder``, ``hilbert_quantise``) fall before the window.

The pinned numbers are what the reducer gave before it learned modules
and program spans (``busy_ns``, ``window_ns``, ``device_ns``, the idle
share), and what the stage view that it absorbed gave (``module_ns``,
the program-span gap labels)."""
import pathlib

import numpy as np
import pytest

from bench import harness, trace

DATA = pathlib.Path(__file__).parent / "data"
FIXTURE = DATA / "fixture.xplane.pb"
JOIN = DATA / "stages.xplane.pb"
TRACES = [FIXTURE, JOIN]
JOIN_STAGES = {"hilbert_sort", "simjoin_permute", "simjoin_totals",
               "simjoin_emit_swizzled", "simjoin_compact", "simjoin_map_back"}
JOIN_SPANS = {"simjoin.pairs", "simjoin.order", "simjoin.schedule", "simjoin.pass1",
              "simjoin.sync", "simjoin.table", "simjoin.pass2", "simjoin.compact",
              "simjoin.map_back"}

PINNED = {
    FIXTURE.name: {
        "window_ns": 73116575.0, "busy_ns": 11869.0, "idle_share": 0.9998376701862745,
        "n_devices": 1,
        "device_ns": {"fusion": 11822.0, "copy-start": 39.0, "copy-done": 8.0},
        "n_ops": 3, "device_ns_total": 11869.0,
        "module_ns": {"_lambda": 11889.0},
        "n_gaps": 9, "gap_ns_total": 73104706.0,
        # every gap of 1 us or more, longest first
        "gaps": [("make_data", 29565058.0), ("make_data", 21639481.0),
                 ("make_data", 19746330.0), ("solve", 2153829.0)],
        "top_ops": [["_lambda/fusion", 1.1822e-05], ["_lambda/copy-start", 3.9e-08],
                    ["_lambda/copy-done", 8e-09]],
    },
    JOIN.name: {
        "window_ns": 24937791.0, "busy_ns": 18896047.0, "idle_share": 0.24227262150043682,
        "n_devices": 1,
        "device_ns": {"fusion.2": 18301213.0, "reduce-window": 365091.0, "fusion": 79962.0,
                      "simjoin_hits.1": 28527.0},
        "n_ops": 64, "device_ns_total": 18895498.0,
        "module_ns": {"hilbert_sort": 6728.0, "simjoin_permute": 5698.0,
                      "simjoin_totals": 31267.0, "simjoin_emit_swizzled": 23844.0,
                      "simjoin_compact": 18743185.0, "simjoin_map_back": 85625.0},
        "n_gaps": 59, "gap_ns_total": 6041744.0,
        "gaps": [("solve", 1969478.0), ("simjoin.pass1", 1558503.0),
                 ("simjoin.order", 1020557.0), ("simjoin.sync", 724211.0),
                 ("make_data", 512703.0), ("make_data", 250102.0), ("sync", 6109.0)],
        "top_ops": [["simjoin_compact/fusion.2", 0.018301213],
                    ["simjoin_compact/reduce-window", 0.000365091],
                    ["simjoin_map_back/fusion", 7.3433e-05],
                    ["simjoin_totals/simjoin_hits.1", 2.8527e-05]],
    },
}


@pytest.fixture(scope="module", params=TRACES, ids=lambda p: p.name)
def recorded(request):
    if not request.param.exists():
        pytest.skip("no recorded trace")
    return request.param, trace.summarize(str(request.param))


@pytest.fixture(scope="module")
def summary():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace")
    return trace.summarize(str(FIXTURE))


@pytest.fixture(scope="module")
def join_summary():
    if not JOIN.exists():
        pytest.skip("no recorded join trace")
    return trace.summarize(str(JOIN))


# --- synthetic intervals and names --------------------------------------------

def test_union_merges_overlaps_and_touching():
    assert trace._union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [[0, 4], [5, 9]]
    assert trace._union([]) == []


def test_label_prefers_cover_then_the_innermost_span():
    spans = [("solve", 0, 100), ("sync", 40, 100), ("make_data", 100, 120)]
    assert trace._label(spans, 50, 90) == "sync"  # inside solve and sync
    assert trace._label(spans, 10, 30) == "solve"
    assert trace._label(spans, 95, 119) == "make_data"  # 19 ns of it, 5 of sync
    assert trace._label(spans, 130, 140) == "host"


def test_program_spans_label_a_gap_by_their_innermost_time():
    bench = [("solve", 0, 100)]
    program = [("simjoin.pairs", 5, 95), ("simjoin.sync", 10, 30), ("simjoin.table", 30, 60)]
    assert trace._label(bench, 12, 40, program) == "simjoin.sync"  # 18 ns of sync, 10 of table
    assert trace._label(bench, 25, 60, program) == "simjoin.table"
    assert trace._label(bench, 60, 99, program) == "simjoin.pairs"  # 35 ns, 4 of solve
    assert trace._label(bench, 96, 100, program) == "solve"
    assert trace._label(bench + [("make_data", 100, 120)], 101, 110, program) == "make_data"


def test_stage_names_strip_jit_and_the_fingerprint():
    assert trace.stage("jit_simjoin_compact(123456)") == "simjoin_compact"
    assert trace.stage("jit__lambda(8485634492780914798)") == "_lambda"
    assert trace.stage("simjoin_compact") == "simjoin_compact"


def test_the_span_rule():
    for name in JOIN_SPANS | {"order_cache.hits", "serve.admit"}:
        assert trace.is_program_span(name), name
    for name in ["window", "solve", "sync", "PjitFunction(f)", "$pjit.py:250 cache_miss",
                 "tpu::System::Execute=>Done", "ThreadpoolListener::Record",
                 "PJRT_LoadedExecutable_Execute", "jit_simjoin_compact(1)", "Simjoin.Pass1"]:
        assert not trace.is_program_span(name), name


# --- both recorded traces: the numbers pinned before the fold ----------------

def _host_events(path):
    from jax.profiler import ProfileData

    return [ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:") for line in plane.lines for ev in line.events]


def test_no_runtime_host_event_matches_the_span_rule(recorded):
    path, _ = recorded
    names = {n for n in _host_events(path) if trace.is_program_span(n)}
    assert names <= JOIN_SPANS


def test_window_busy_and_device_time_are_pinned(recorded):
    path, s = recorded
    want = PINNED[path.name]
    assert s.window_ns == want["window_ns"]
    assert s.busy_ns == want["busy_ns"]
    assert s.idle_share == want["idle_share"]
    assert s.n_devices == want["n_devices"]
    assert len(s.device_ns) == want["n_ops"]
    assert sum(s.device_ns.values()) == want["device_ns_total"]
    for op, ns in want["device_ns"].items():
        assert s.device_ns[op] == ns, op


def test_module_time_and_gap_labels_are_pinned(recorded):
    path, s = recorded
    want = PINNED[path.name]
    assert s.module_ns == want["module_ns"]
    assert len(s.gaps) == want["n_gaps"]
    assert sum(ns for _, ns in s.gaps) == want["gap_ns_total"]
    assert [(label, ns) for label, ns in s.gaps if ns >= 1000] == want["gaps"]
    assert s.top_ops(len(want["top_ops"])) == want["top_ops"]


def test_stage_split_adds_up_to_the_device_time(recorded):
    _, s = recorded
    per_op: dict = {}
    for key, ns in s.stage_op_ns.items():
        op = key.split("/", 1)[1]
        per_op[op] = per_op.get(op, 0) + ns
    assert per_op == pytest.approx(s.device_ns)
    assert set(k.split("/")[0] for k in s.stage_op_ns) <= set(s.module_ns) | {"?"}


# --- the small fixture, against the raw events ------------------------------

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


def test_ops_of_an_anonymous_jit_belong_to_its_stage(summary):
    assert set(summary.module_ns) == {"_lambda"}
    assert {k.split("/")[0] for k in summary.stage_op_ns} == {"_lambda"}
    assert sum(summary.stage_op_ns.values()) <= summary.module_ns["_lambda"] * 1.01
    assert summary.gap_ns_labelled("simjoin.") == 0


# --- the recorded join --------------------------------------------------------

def test_join_trace_names_every_device_stage(join_summary):
    mods = join_summary.module_ns
    assert JOIN_STAGES <= set(mods)
    ops = sum(join_summary.stage_op_ns.values())
    named = sum(ns for k, ns in join_summary.stage_op_ns.items()
                if k.split("/")[0] in JOIN_STAGES)
    assert named >= 0.95 * ops
    assert {k.split("/")[0] for k in join_summary.stage_op_ns} <= set(mods)


def test_join_trace_gaps_carry_program_spans(join_summary):
    labels = [label for label, ns in join_summary.gaps if ns >= 1e6]
    assert {"simjoin.order", "simjoin.pass1"} <= set(labels)
    assert join_summary.spans["simjoin.pairs"] == join_summary.spans["solve"] == 1


def test_join_trace_stage_metrics():
    """The readers of the ε-join's stage metrics on the recorded join, one
    solve in the window."""
    ev = {"trace": trace.summarize(str(JOIN)), "solves": 1}
    assert harness.reader("simjoin.compact_ms")(ev) == 18743185.0 / 1e6
    # pass 1 (1.56 ms), the order (1.02 ms) and the totals' download (0.72 ms)
    assert harness.reader("simjoin.host_wait_ms")(ev) == pytest.approx(3.303312)
    assert harness.reader("simjoin.pass1_ms")(ev) == 28527.0 / 1e6
    assert harness.reader("idle_share")(ev) == 100.0 * 0.24227262150043682
    # the fixture holds no join: nothing to read
    ev = {"trace": trace.summarize(str(FIXTURE)), "solves": 3}
    for name in ("simjoin.compact_ms", "simjoin.host_wait_ms", "simjoin.pass1_ms"):
        assert harness.reader(name)(ev) is None, name
    for name in ("simjoin.compact_ms", "simjoin.host_wait_ms"):
        assert harness.reader(name)({"trace": None, "solves": 3}) is None
