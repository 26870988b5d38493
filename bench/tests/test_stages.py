"""The stage view of a trace (``bench/stages.py``) and the readers of the
program's counters, on the two recorded TPU v5e traces and on a CPU join.

``data/fixture.xplane.pb`` (see ``test_trace.py``) runs one anonymous
jit.  ``data/stages.xplane.pb`` is a traced run of the eps-k6 cell at
2,048 points on a TPU v5e: ``harness.run_cell("simjoin-syn3d.eps-k6",
2147483661, 0.02, True, overrides={"config": {"n_points": 2048}})``
with the file kept.  Its window holds one join with the program's spans
and stage modules.  Host threads that held only compiler passes and
transfers were dropped from it to keep it small; the device plane and
the Python, main and task threads are as recorded.  In it the device's
clock runs about 1.2 ms ahead of the host's, so the join's first two
modules (``reorder``, ``hilbert_quantise``) fall before the window."""
import pathlib

import pytest

from bench import harness, stages, trace

DATA = pathlib.Path(__file__).parent / "data"
FIXTURES = [DATA / "fixture.xplane.pb", DATA / "stages.xplane.pb"]
JOIN_STAGES = {"hilbert_sort", "simjoin_permute", "simjoin_totals",
               "simjoin_emit_swizzled", "simjoin_compact", "simjoin_map_back"}
JOIN_SPANS = {"simjoin.pairs", "simjoin.order", "simjoin.schedule", "simjoin.pass1",
              "simjoin.sync", "simjoin.table", "simjoin.pass2", "simjoin.compact",
              "simjoin.map_back"}


def _host_events(path):
    from jax.profiler import ProfileData

    return [ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:") for line in plane.lines for ev in line.events]


def test_stage_names_strip_jit_and_the_fingerprint():
    assert stages.stage("jit_simjoin_compact(123456)") == "simjoin_compact"
    assert stages.stage("jit__lambda(8485634492780914798)") == "_lambda"
    assert stages.stage("simjoin_compact") == "simjoin_compact"


def test_the_span_rule():
    for name in JOIN_SPANS | {"order_cache.hits", "serve.admit"}:
        assert stages.is_program_span(name), name
    for name in ["window", "solve", "sync", "PjitFunction(f)", "$pjit.py:250 cache_miss",
                 "tpu::System::Execute=>Done", "ThreadpoolListener::Record",
                 "PJRT_LoadedExecutable_Execute", "jit_simjoin_compact(1)", "Simjoin.Pass1"]:
        assert not stages.is_program_span(name), name


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_no_runtime_host_event_matches_the_span_rule(path):
    names = {n for n in _host_events(path) if stages.is_program_span(n)}
    assert names <= JOIN_SPANS


def test_program_spans_label_a_gap_by_their_innermost_time():
    bench = [("solve", 0, 100)]
    program = [("simjoin.pairs", 5, 95), ("simjoin.sync", 10, 30), ("simjoin.table", 30, 60)]
    assert stages._label(bench, program, 12, 40) == "simjoin.sync"  # 18 ns of sync, 10 of table
    assert stages._label(bench, program, 25, 60) == "simjoin.table"
    assert stages._label(bench, program, 60, 99) == "simjoin.pairs"  # 35 ns, 4 of solve
    assert stages._label(bench, program, 96, 100) == "solve"
    assert stages._label(bench + [("make_data", 100, 120)], program, 101, 110) == "make_data"


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_gaps_and_device_time_are_those_of_bench_trace(path):
    st, tr = stages.summarize(str(path)), trace.summarize(str(path))
    assert st["window_ns"] == tr.window_ns
    assert sorted(ns for _, ns in st["gaps"]) == sorted(ns for _, ns in tr.gaps)
    per_op: dict = {}
    for key, ns in st["device_ns"].items():
        op = key.split("/", 1)[1]
        per_op[op] = per_op.get(op, 0) + ns
    assert per_op == pytest.approx(tr.device_ns)
    assert st["solves"] == tr.spans["solve"]


def test_ops_of_an_anonymous_jit_belong_to_its_stage():
    st = stages.summarize(str(FIXTURES[0]))
    assert set(st["module_ns"]) == {"_lambda"}
    assert {k.split("/")[0] for k in st["device_ns"]} == {"_lambda"}
    assert sum(st["device_ns"].values()) <= st["module_ns"]["_lambda"] * 1.01
    # no program span: every gap keeps its bench/trace.py label
    assert [g[0] for g in st["gaps"]] == [g[0] for g in trace.summarize(str(FIXTURES[0])).gaps]


@pytest.fixture(scope="module")
def join_trace():
    if not FIXTURES[1].exists():
        pytest.skip("no recorded join trace")
    return stages.summarize(str(FIXTURES[1]))


def test_join_trace_names_every_device_stage(join_trace):
    mods = join_trace["module_ns"]
    assert JOIN_STAGES <= set(mods)
    ops = sum(join_trace["device_ns"].values())
    named = sum(ns for k, ns in join_trace["device_ns"].items()
                if k.split("/")[0] in JOIN_STAGES)
    assert named >= 0.95 * ops
    assert {k.split("/")[0] for k in join_trace["device_ns"]} <= set(mods)


def test_join_trace_gaps_carry_program_spans(join_trace):
    labels = [label for label, ns in join_trace["gaps"] if ns >= 1e6]
    assert {"simjoin.order", "simjoin.pass1"} <= set(labels)
    rep = stages.report(join_trace)
    assert rep["simjoin.compact_ms"] > 0 and rep["simjoin.host_wait_ms"] > 0
    assert rep["solves"] == join_trace["solves"] >= 1


def test_counter_readers_read_the_programs_counters():
    import jax.numpy as jnp
    import numpy as np

    from repro.core.tracing import counters
    from repro.kernels import ops

    x = np.random.default_rng(1).integers(0, 32, (1024, 3)).astype(np.float32)
    ops.simjoin_pairs(jnp.asarray(x), 2.5, hilbert_order=True)
    c = counters()
    tile = harness.reader("simjoin.tile_yield")({})
    compact = harness.reader("simjoin.compact_yield")({})
    assert tile == pytest.approx(100.0 * c["simjoin.tiles_live"] / c["simjoin.tile_pairs"])
    assert compact == pytest.approx(
        100.0 * c["simjoin.pairs_out"] / c["simjoin.mask_cells_scanned"])
    assert 0 < tile <= 100 and 0 < compact <= 100
