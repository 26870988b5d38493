"""Whole runs of every cell on the CPU at a test size, with the chip check
skipped: the sound program reads ``correct``; the timed path broken
underneath (each fault a cell can have) or the reference computed one
precision lower put in its place (the control) reads not correct."""
import io

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import kmeans as kref
from bench.reference import simjoin as sref

SMALL = {
    "kmeans": {"config": {"n_points": 4096}, "traffic": {"k": 40, "iters": 3}},
    "simjoin": {"config": {"n_points": 2048}},
}
# the benchmark's cells, and the k-means cells its pieces are kept for
# (out of BENCHMARK.json until a comparison separates its control)
SPEC = harness.load_spec()
SPEC["configs"].append({"name": "kmeans-census1990",
                        "file": "bench/configs/kmeans-census1990.json"})
SPEC["workloads"] += [{"name": f"kmeans-census1990.{t}", "config": "kmeans-census1990",
                       "traffic": t, "chips": 1} for t in ("fit-k1024", "fit-k20")]
CELLS = [w["name"] for w in SPEC["workloads"]]


def run(cell, seed=12345, **kw):
    app = cell.split("-")[0]
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(cell, seed, 0.3, False, spec=SPEC, overrides=SMALL[app],
                           require_chip=False, out=out, err=err, **kw)
    return res, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    res, out, err = run(cell)
    assert res["correct"], err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert list(res)[-1] == "checks"
    # the numbers compared are the last lines of standard error
    assert err.strip().splitlines()[-1].startswith("check ")
    assert out.strip().splitlines()[-1].startswith('{"correct": true')


# --- faults planted in the timed path ---------------------------------------

def _kmeans_fault(kind):
    from repro.kernels import ops

    real = ops.kmeans_lloyd

    def broken(x, k, **kw):
        c, a = real(x, k, **kw)
        if kind == "state_unchanged":  # every step returns its state: init centroids
            c = kref.init_centroids(x, k, kw["seed"])
            a = kref.assign(x, c)
        elif kind == "half_batch":  # the fit over the first half of the points alone
            c, _ = real(x[: x.shape[0] // 2], k, **kw)
            a = kref.assign(x, c)
        elif kind == "answer_altered":  # one centroid moved where it is produced
            c = c.at[3, 5].add(0.05 * jnp.max(jnp.abs(c)))
        return c, a

    return broken


def _simjoin_fault(kind):
    from repro.kernels import ops

    real = ops.simjoin_pairs

    def broken(x, eps, **kw):
        if kind == "half_batch":
            return real(x[: x.shape[0] // 2], eps, **kw)
        p = real(x, eps, **kw)
        # answer_altered: one pair's second id moved where it is produced
        return p.at[0, 1].set((p[0, 1] + 7) % p[0, 0])

    return broken


FAULTS = [
    ("kmeans-census1990.fit-k20", "kmeans_lloyd", _kmeans_fault, k)
    for k in ("state_unchanged", "half_batch", "answer_altered")
] + [
    ("kmeans-census1990.fit-k1024", "kmeans_lloyd", _kmeans_fault, "answer_altered"),
    ("simjoin-syn3d.eps-k100", "simjoin_pairs", _simjoin_fault, "half_batch"),
    ("simjoin-syn3d.eps-k100", "simjoin_pairs", _simjoin_fault, "answer_altered"),
    ("simjoin-syn3d.eps-k6", "simjoin_pairs", _simjoin_fault, "answer_altered"),
]


@pytest.mark.parametrize("cell,entry,make,kind", FAULTS,
                         ids=[f"{c}-{k}" for c, _, _, k in FAULTS])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, entry, make, kind):
    from repro.kernels import ops

    monkeypatch.setattr(ops, entry, make(kind))
    res, _, err = run(cell)
    assert not res["correct"], err
    assert res["failed"] >= 1


# --- the control ------------------------------------------------------------

def _kmeans_control(x, k, *, iters, seed, **_):
    return kref.lloyd(x, k, iters, seed, precision="high")


def _simjoin_control(x, eps, **_):
    return sref.pairs(x, eps * eps, precision="high")


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(monkeypatch, cell):
    from repro.kernels import ops

    if cell.startswith("kmeans"):
        monkeypatch.setattr(ops, "kmeans_lloyd", _kmeans_control)
    else:
        monkeypatch.setattr(ops, "simjoin_pairs", _simjoin_control)
    res, _, err = run(cell)
    assert not res["correct"], err
