"""Everything BENCHMARK.json names is found by name, and nothing more is
needed to add a cell."""
import json

import pytest

from bench import harness

SPEC = harness.load_spec()
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


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metrics_of_each_cell(cell):
    layer = {m["name"] for m in harness.resolve(SPEC, cell)["per_layer"]}
    app = cell.split("-")[0]
    want = {"idle_share", "compiles_in_window", f"{app}_roofline",
            {"kmeans": "kmeans.lloyd_ms", "simjoin": "simjoin.pass1_ms"}[app]}
    assert layer == want
    for m in SPEC["per_layer"]:
        assert m["moves"] == "solve_s"


def test_a_new_cell_is_data_only():
    """A cell of a configuration and traffic that are only files under
    bench/ resolves once the spec names it, without touching any code."""
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "kmeans-census1990",
                            "file": "bench/configs/kmeans-census1990.json"})
    spec["workloads"].append({"name": "kmeans-census1990.fit-k20", "config": "kmeans-census1990",
                              "traffic": "fit-k20", "chips": 1})
    c = harness.resolve(spec, "kmeans-census1990.fit-k20")
    assert c["config"]["n_points"] == 2458285 and c["traffic"]["k"] == 20
    assert {m["name"] for m in c["per_layer"]} == {"idle_share", "compiles_in_window"}


def test_peaks_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert harness.peaks("cpu") is None


def test_seeds_beyond_32_bits_differ():
    a, b = harness.Seeds(7), harness.Seeds(2**33 + 7)
    assert a.int31("job", 0) != b.int31("job", 0)
    assert harness.Seeds(2**33 + 7).int31("job", 0) == b.int31("job", 0)
