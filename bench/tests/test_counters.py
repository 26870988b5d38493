"""The program's counters reach the readers as the window's counts: the
harness differences them over the window, and the yield readers divide
them."""
import io
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness


class _CountingCell:
    """A stand-in app whose warm-up counts other tiles than its window's
    joins: 1000 tile pairs all live, then 100 pairs with 5 live."""

    def __init__(self, cfg, traffic, seeds):
        pass

    def job(self, i):
        return i

    def solve(self, job):
        from repro.core.tracing import count

        warm = job == "warmup"
        count("simjoin.tile_pairs", 1000 if warm else 100)
        count("simjoin.tiles_live", 1000 if warm else 5)
        count("simjoin.mask_cells_scanned", 4096)
        count("simjoin.pairs_out", 4096 if warm else 512)
        return jnp.zeros(4) + (0 if warm else job)

    def size(self, res):
        return int(res.shape[0])

    def work(self, sizes):
        return {"flops": 0.0, "bytes": 1.0}

    def check(self, job, res):
        return {"mismatched": 0}


def test_counters_are_differenced_over_the_window(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.apps.counting",
                        types.SimpleNamespace(Cell=_CountingCell))
    seen = []
    real = harness.reader
    monkeypatch.setattr(harness, "reader", lambda name: lambda ev: seen.append(ev) or 1.0)
    res = harness.run_cell("simjoin-syn3d.eps-k6", 7, 0.05, False,
                           overrides={"config": {"app": "counting"}}, require_chip=False,
                           out=io.StringIO(), err=io.StringIO())
    assert res["correct"]
    ev = seen[0]
    n = ev["solves"]
    assert n >= 1
    c = ev["counters"]
    assert c["simjoin.tile_pairs"] == 100 * n and c["simjoin.tiles_live"] == 5 * n
    assert real("simjoin.tile_yield")(ev) == pytest.approx(5.0)
    assert real("simjoin.compact_yield")(ev) == pytest.approx(12.5)


def test_counter_readers_read_the_programs_counters():
    from repro.core.tracing import counters
    from repro.kernels import ops

    x = np.random.default_rng(1).integers(0, 32, (1024, 3)).astype(np.float32)
    c0 = counters()
    ops.simjoin_pairs(jnp.asarray(x), 2.5, hilbert_order=True)
    c = {k: v - c0.get(k, 0) for k, v in counters().items()}
    ev = {"counters": c}
    tile = harness.reader("simjoin.tile_yield")(ev)
    compact = harness.reader("simjoin.compact_yield")(ev)
    assert tile == pytest.approx(100.0 * c["simjoin.tiles_live"] / c["simjoin.tile_pairs"])
    assert compact == pytest.approx(
        100.0 * c["simjoin.pairs_out"] / c["simjoin.mask_cells_scanned"])
    assert 0 < tile <= 100 and 0 < compact <= 100


def test_counter_readers_find_nothing_without_counts():
    for name in ("simjoin.tile_yield", "simjoin.compact_yield"):
        assert harness.reader(name)({"counters": {}}) is None
        assert harness.reader(name)({}) is None
