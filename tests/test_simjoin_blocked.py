"""The ε-join's pruned schedule and its blocked passes, on the CPU.

``ops.simjoin_pairs`` builds its tile-pair schedule on the device (the
lower-triangle tile pairs whose boxes lie within ε, in curve order) and
runs pass 1 and pass 2 in fixed blocks.  Here the block sizes are shrunk
with ``monkeypatch`` so that a 3,000-point join (ragged at bp = 128)
runs each pass in several blocks, and everything is checked against
numbers computed from the inputs alone.
"""
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CURVES, triangle_schedule
from repro.core.tracing import counters
from repro.kernels import ops, ref
from repro.kernels import simjoin as sj
from repro.kernels.kmeans import hilbert_point_order

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench.reference import simjoin as bench_ref  # noqa: E402

N, SIDE, BP, EPS2 = 3000, 64, 128, 20.5  # 3000 = 23·128 + 56: ragged
PT = -(-N // BP)


@pytest.fixture(scope="module")
def points():
    return np.random.default_rng(3).integers(0, SIDE, (N, 3)).astype(np.float32)


def _padded(x):
    return jnp.asarray(np.pad(x, ((0, PT * BP - N), (0, 0))))


def _exact_pairs(x):
    """Every unordered pair within ε as (i, j), i > j, brute force."""
    xi = x.astype(np.int64)
    out = []
    for a in range(0, len(x), 500):
        d2 = ((xi[a:a + 500, None, :] - xi[None, :, :]) ** 2).sum(-1)
        i, j = np.nonzero(d2 <= EPS2)
        keep = i + a > j
        out.append(np.stack([i[keep] + a, j[keep]], axis=1))
    return np.concatenate(out)


def _boxes(x, valid):
    """Per-tile (lo, hi) of the valid rows, numpy."""
    lo = np.full((PT, 3), np.inf)
    hi = np.full((PT, 3), -np.inf)
    for t in range(PT):
        rows = x[t * BP:(t + 1) * BP][valid[t * BP:(t + 1) * BP]]
        lo[t], hi[t] = rows.min(axis=0), rows.max(axis=0)
    return lo, hi


def _box_reach(lo, hi):
    g = np.maximum(np.maximum(lo[:, None] - hi[None], lo[None] - hi[:, None]), 0)
    return (g * g).sum(-1) <= EPS2


@pytest.mark.parametrize("curve", CURVES)
def test_pruned_schedule_is_the_triangle_less_its_pruned_rows(points, curve):
    x = np.asarray(points)[np.asarray(hilbert_point_order(jnp.asarray(points)))]
    sched, kept = sj.pruned_schedule(
        _padded(x), eps=EPS2 ** 0.5, bp=BP, n_valid=N, curve=curve
    )
    keep = np.asarray(sj._reach(_padded(x), eps=EPS2 ** 0.5, bp=BP, n_valid=N))
    tri = triangle_schedule(curve, PT, strict=False)
    want = tri[keep[tri[:, 0], tri[:, 1]]]
    sched = np.asarray(sched)
    assert kept == len(want) == int(keep.sum())
    assert 0 < kept < len(tri)  # some pruned, some kept
    assert sched.shape[0] == sj._schedule_cap(kept) >= kept * 5 // 4
    np.testing.assert_array_equal(sched[:kept], want)
    assert (sched[kept:] == 0).all()  # padding rows, never run


def test_every_tile_pair_holding_a_pair_is_kept_at_a_ragged_n(points):
    """Points far from the origin, so the zero pad rows of the ragged last
    tile would stretch its box across the grid: the box leaves them out,
    and still no tile pair that holds a pair is pruned."""
    x = np.asarray(points) + 1000.0
    x = x[np.asarray(hilbert_point_order(jnp.asarray(x)))]
    keep = np.asarray(sj._reach(_padded(x), eps=EPS2 ** 0.5, bp=BP, n_valid=N))
    pairs = _exact_pairs(x)
    holding = np.zeros((PT, PT), bool)
    holding[pairs[:, 0] // BP, pairs[:, 1] // BP] = True
    assert holding.any() and not (holding & ~keep).any()
    # the last tile's box is that of its valid rows only
    valid = np.arange(PT * BP) < N
    lo, hi = _boxes(np.pad(x, ((0, PT * BP - N), (0, 0))), valid)
    np.testing.assert_array_equal(keep[-1], np.tril(_box_reach(lo, hi))[-1])
    with_pads = _boxes(np.pad(x, ((0, PT * BP - N), (0, 0))), np.ones(PT * BP, bool))
    assert np.tril(_box_reach(*with_pads))[-1].sum() > keep[-1].sum()


@pytest.fixture
def small_blocks(monkeypatch):
    """Pass 1 in blocks of 32 steps, pass 2 in blocks of 16 table rows."""
    monkeypatch.setattr(sj, "PASS1_STEPS", 32)
    monkeypatch.setattr(sj, "PASS2_CELLS", 16 * BP * BP)


@pytest.mark.parametrize("hilbert_order", [True, False], ids=["hilbert", "input"])
def test_blocked_join_is_exact(points, small_blocks, hilbert_order):
    x = jnp.asarray(points)
    before = counters()
    got = np.asarray(ops.simjoin_pairs(x, EPS2 ** 0.5, bp=BP, hilbert_order=hilbert_order))
    d = {k: v - before.get(k, 0) for k, v in counters().items()}
    exact = _exact_pairs(np.asarray(points))
    want = bench_ref.pair_count(x, EPS2)
    assert want == len(exact)
    assert bench_ref.compare(x, got, want, EPS2)["mismatched"] == 0
    oracle = np.asarray(ref.simjoin_pairs(x, EPS2 ** 0.5))
    assert {tuple(p) for p in got.tolist()} == {tuple(p) for p in oracle.tolist()}

    # the counters, from the inputs
    order = (np.asarray(hilbert_point_order(x)) if hilbert_order
             else np.arange(N))
    inv = np.argsort(order)
    local = inv[exact]  # pair ids in the join's point order
    live = len({(max(a, b) // BP, min(a, b) // BP) for a, b in local.tolist()})
    xs = np.asarray(points)[order]
    keep = np.asarray(sj._reach(_padded(xs), eps=EPS2 ** 0.5, bp=BP, n_valid=N))
    kept = int(keep.sum())
    rows = 16
    assert d["simjoin.tri_pairs"] == PT * (PT + 1) // 2
    assert d["simjoin.tile_pairs"] == kept
    assert d["simjoin.pass1_blocks"] == -(-kept // 32) >= 3
    assert d["simjoin.pass1_steps"] == kept  # the last block runs its live steps
    assert d["simjoin.tiles_live"] == live
    assert d["simjoin.pass2_blocks"] == -(-live // rows) >= 3
    assert d["simjoin.pairs_out"] == len(exact)
    assert d["simjoin.mask_rows_scanned"] == rows * d["simjoin.pass2_blocks"]
    assert d["simjoin.mask_cells_scanned"] == d["simjoin.mask_rows_scanned"] * BP * BP
    assert d["simjoin.rows_flattened"] == d["simjoin.mask_rows_scanned"] * BP


def test_blocks_keep_the_one_block_order(points, small_blocks):
    """Blocked and one-block joins emit the same pairs in the same order:
    schedule, then row-major within each tile."""
    x = jnp.asarray(points)
    blocked = np.asarray(ops.simjoin_pairs(x, EPS2 ** 0.5, bp=BP, hilbert_order=True))
    sj.PASS1_STEPS, sj.PASS2_CELLS = 1 << 16, 1 << 29  # restored by monkeypatch
    whole = np.asarray(ops.simjoin_pairs(x, EPS2 ** 0.5, bp=BP, hilbert_order=True))
    np.testing.assert_array_equal(blocked, whole)


@pytest.mark.parametrize("start, live", [(0, 6), (0, 2), (3, 1), (2, 4)])
def test_a_block_counts_its_live_steps_only(points, start, live):
    """A pass-1 block of one compiled size runs ``live`` steps from
    ``start``: each counts its own tile pair, and the rows past ``live``
    read zero, whatever the schedule holds there."""
    x = jnp.asarray(np.pad(np.asarray(points), ((0, PT * BP - N), (0, 0))))
    sched = jnp.asarray([[0, 0], [1, 0], [1, 1], [2, 1], [2, 2], [3, 2]], jnp.int32)
    whole = np.asarray(sj.simjoin_totals(
        sched, 0, 6, x, steps=6, eps=EPS2 ** 0.5, bp=BP, n_valid=N, interpret=True,
    ))
    want = np.asarray(sj.simjoin_tile_hits_swizzled(
        sched, x, eps=EPS2 ** 0.5, bp=BP, n_valid=N, interpret=True,
    )[0]).sum(axis=1)
    np.testing.assert_array_equal(whole, want)
    assert (want > 0).all()
    got = np.asarray(sj.simjoin_totals(
        sched, start, live, x, steps=6 - start, eps=EPS2 ** 0.5, bp=BP,
        n_valid=N, interpret=True,
    ))
    np.testing.assert_array_equal(got[:live], want[start:start + live])
    assert (got[live:] == 0).all()
