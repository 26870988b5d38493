"""Everything BENCHMARK.json names is found by name, and nothing more is
needed to add a cell."""
import json

import pytest

from bench import harness

SPEC = harness.load_spec()
ROOT = harness.ROOT
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(harness.reader(m["name"])), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.resolve(SPEC, cell)
    assert c["config"]["app"] == c["traffic"]["app"]
    mod = __import__(f"bench.apps.{c['config']['app']}", fromlist=["Cell"])
    assert hasattr(mod, "Cell")
    assert [m["name"] for m in c["end_to_end"]] == ["solve_s", "setup_s"]
    assert set(c["traffic"]["limits"])  # every cell compares something


def _expected_per_layer(spec, cell):
    """The cell's per-layer metrics by resolve's rule, read off the spec: an
    entry with a ``workloads`` list where the list names the cell, else one
    whose ``moves`` the cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
    return {m["name"] for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)}


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metrics_of_each_cell(cell):
    layer = {m["name"] for m in harness.resolve(SPEC, cell)["per_layer"]}
    assert layer == _expected_per_layer(SPEC, cell)
    assert layer  # every cell reports a per-layer metric
    e2e = {m["name"] for m in harness.resolve(SPEC, cell)["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        if m["name"] in layer:
            assert m["moves"] in e2e, m["name"]


# the accepted cells' per-layer metrics, pinned; an entry added later that
# names one of them in its own ``workloads`` list joins it
PINNED = {
    cell: {"idle_share", "compiles_in_window", "simjoin_roofline", "simjoin.pass1_ms",
           "simjoin.compact_ms", "simjoin.host_wait_ms", "simjoin.tile_yield",
           "simjoin.compact_yield"}
    for cell in ("simjoin-syn3d.eps-k100", "simjoin-syn3d.eps-k6")
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_per_layer_metrics_of_the_accepted_cells(cell):
    layer = {m["name"] for m in harness.resolve(SPEC, cell)["per_layer"]}
    added = {m["name"] for m in SPEC["per_layer"]
             if cell in m.get("workloads", []) and m["name"] not in PINNED[cell]}
    assert layer - added == PINNED[cell]


def test_a_new_cell_is_data_only(tmp_path):
    """A cell of a configuration and traffic that are only files resolves
    once the spec names it, without touching any code: a k-means cell of
    the files under bench/, and an ε-join cell of a configuration written
    elsewhere with a per-layer metric of its own."""
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "kmeans-census1990",
                            "file": "bench/configs/kmeans-census1990.json"})
    spec["workloads"].append({"name": "kmeans-census1990.fit-k20", "config": "kmeans-census1990",
                              "traffic": "fit-k20", "chips": 1})
    c = harness.resolve(spec, "kmeans-census1990.fit-k20")
    assert c["config"]["n_points"] == 2458285 and c["traffic"]["k"] == 20
    assert {m["name"] for m in c["per_layer"]} == {"idle_share", "compiles_in_window"}

    conf = json.loads((ROOT / "bench/configs/simjoin-syn3d.json").read_text())
    conf.update(n_points=2000000, reduced=[])
    (tmp_path / "bench/configs").mkdir(parents=True)
    (tmp_path / "bench/configs/simjoin-big.json").write_text(json.dumps(conf))
    spec["configs"].append({"name": "simjoin-big", "file": "bench/configs/simjoin-big.json"})
    spec["workloads"].append({"name": "simjoin-big.eps-k6", "config": "simjoin-big",
                              "traffic": "eps-k6", "chips": 1})
    spec["per_layer"].append({"name": "simjoin.reach_share", "unit": "%", "better": "higher",
                              "source": "program_counter", "layer": "kernels", "moves": "solve_s",
                              "workloads": ["simjoin-big.eps-k6"]})
    c = harness.resolve(spec, "simjoin-big.eps-k6", root=tmp_path)
    assert c["config"]["n_points"] == 2000000 and c["traffic"]["neighbours"] == 6
    assert [m["name"] for m in c["end_to_end"]] == ["solve_s", "setup_s"]
    assert {m["name"] for m in c["per_layer"]} == {"idle_share", "compiles_in_window",
                                                   "simjoin.reach_share"}
    # the accepted cells do not take it on
    for cell in PINNED:
        assert "simjoin.reach_share" not in {m["name"] for m in harness.resolve(spec, cell)["per_layer"]}


def test_peaks_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert harness.peaks("cpu") is None


def test_seeds_beyond_32_bits_differ():
    a, b = harness.Seeds(7), harness.Seeds(2**33 + 7)
    assert a.int31("job", 0) != b.int31("job", 0)
    assert harness.Seeds(2**33 + 7).int31("job", 0) == b.int31("job", 0)
