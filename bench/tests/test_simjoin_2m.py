"""The ε-join at its published 2,000,000 points: the cell
``simjoin-syn3d-2m.eps-k6`` is files and entries only, and its three
metrics read the program's schedule counters and stages."""
import io
import json

import pytest

from bench import harness
from bench.trace import TraceSummary

SPEC = harness.load_spec()
CELL = "simjoin-syn3d-2m.eps-k6"
NEW = {"simjoin.reach_share", "simjoin.schedule_ms", "simjoin.pass1_us_step"}
ACCEPTED = {"idle_share", "compiles_in_window", "simjoin_roofline", "simjoin.pass1_ms",
            "simjoin.compact_ms", "simjoin.host_wait_ms", "simjoin.tile_yield",
            "simjoin.compact_yield"}


def test_the_cell_resolves_from_files_alone():
    c = harness.resolve(SPEC, CELL)
    assert c["config"]["n_points"] == 2_000_000 and c["config"]["reduced"] == []
    assert c["config"]["data_seed"] == 1990 and c["traffic"]["neighbours"] == 6
    assert c["workload"]["chips"] == 1
    assert [m["name"] for m in c["end_to_end"]] == ["solve_s", "setup_s"]
    assert {m["name"] for m in c["per_layer"]} == NEW | {"idle_share", "compiles_in_window"}
    conf = {k: v for k, v in c["config"].items() if k not in ("n_points", "reduced")}
    old = harness.resolve(SPEC, "simjoin-syn3d.eps-k6")["config"]
    for key in ("app", "n_dims", "grid_side", "data_seed", "dtype", "precision",
                "guarantee", "published", "assumed"):
        assert conf[key] == old[key], key


@pytest.mark.parametrize("cell", ["simjoin-syn3d.eps-k100", "simjoin-syn3d.eps-k6"])
def test_the_accepted_cells_keep_their_metrics(cell):
    assert {m["name"] for m in harness.resolve(SPEC, cell)["per_layer"]} == ACCEPTED


def _trace(device_ns=None, module_ns=None):
    return TraceSummary(window_ns=1e9, busy_ns=9e8, device_ns=device_ns or {}, gaps=[],
                        n_devices=1, spans={}, module_ns=module_ns or {}, stage_op_ns={})


def test_reach_share_reads_the_counters():
    read = harness.reader("simjoin.reach_share")
    ev = {"counters": {"simjoin.tile_pairs": 131_107 * 4, "simjoin.tri_pairs": 30_525_391 * 4}}
    assert read(ev) == pytest.approx(100 * 131_107 / 30_525_391)
    assert read({"counters": {"simjoin.tile_pairs": 10}}) is None
    assert read({}) is None


def test_schedule_ms_reads_the_stage():
    read = harness.reader("simjoin.schedule_ms")
    ev = {"solves": 4, "trace": _trace(module_ns={"simjoin_schedule": 30e6,
                                                   "simjoin_compact": 1e9})}
    assert read(ev) == pytest.approx(7.5)
    assert read({"solves": 4, "trace": _trace(module_ns={"simjoin_compact": 1e9})}) is None
    assert read({"solves": 4, "trace": None}) is None


def test_pass1_us_step_reads_the_kernel_over_the_steps():
    read = harness.reader("simjoin.pass1_us_step")
    tr = _trace(device_ns={"simjoin_hits.1": 150e6, "simjoin_emit.1": 9e9})
    ev = {"solves": 3, "trace": tr, "counters": {"simjoin.pass1_steps": 3 * 130_694}}
    assert read(ev) == pytest.approx(150e3 / (3 * 130_694))
    assert read({"solves": 3, "trace": tr, "counters": {}}) is None
    assert read({"solves": 3, "trace": _trace(), "counters": ev["counters"]}) is None
    assert read({"solves": 3, "trace": None, "counters": ev["counters"]}) is None


def test_a_small_run_of_the_cell_counts_its_schedule():
    """The cell run whole on the CPU at a ragged test size: correct, and
    its window's counters give the reach share."""
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(CELL, 2**33 + 5, 0.3, False,
                           overrides={"config": {"n_points": 3000}},
                           require_chip=False, out=out, err=err)
    assert res["correct"], err.getvalue()
    window = next(json.loads(ln) for ln in out.getvalue().splitlines()
                  if '"record": "window"' in ln)
    c = window["counters"]
    n = window["solves"]
    pt = -(-3000 // 256)
    assert c["simjoin.tri_pairs"] == n * pt * (pt + 1) // 2
    assert 0 < c["simjoin.tile_pairs"] <= c["simjoin.tri_pairs"]
    assert c["simjoin.pass1_blocks"] == c["simjoin.pass2_blocks"] == n
    share = harness.reader("simjoin.reach_share")({"counters": c})
    assert share == pytest.approx(100 * c["simjoin.tile_pairs"] / c["simjoin.tri_pairs"])
