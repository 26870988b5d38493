"""The program's spans and counters (``repro.core.tracing``) on a small
CPU ε-join: 4,096 points on a 64³ integer grid, sixteen 256-point tiles.

Every counter is checked against a number computed here from the inputs
alone, and the spans against a profiler trace of the join read back with
``jax.profiler.ProfileData``.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tracing import count, counters
from repro.kernels import ops
from repro.kernels.kmeans import _cached_order, hilbert_point_order_cached

N, SIDE, EPS, BP = 4096, 64, 3.5, 256
STAGES = ("simjoin.order", "simjoin.schedule", "simjoin.pass1", "simjoin.sync",
          "simjoin.table", "simjoin.pass2", "simjoin.compact")


@pytest.fixture(scope="module")
def points():
    """Integer points (exact squared distances), sorted by their first
    coordinate so the hits fill a band of tiles around the diagonal."""
    x = np.random.default_rng(0).integers(0, SIDE, (N, 3))
    return x[np.lexsort(x.T[::-1])].astype(np.float32)


def _brute(x):
    """(pair count, tile pairs holding a pair) of the exact join."""
    xi = x.astype(np.int64)
    pairs, tiles = 0, set()
    for a in range(0, N, BP):
        d2 = ((xi[a:a + BP, None, :] - xi[None, :, :]) ** 2).sum(-1)
        i, j = np.nonzero(d2 <= EPS * EPS)
        i = i + a
        keep = i > j
        pairs += int(keep.sum())
        tiles |= set(zip((i[keep] // BP).tolist(), (j[keep] // BP).tolist()))
    return pairs, len(tiles)


def _delta(before):
    after = counters()
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


def _box_pairs(x):
    """Lower-triangle tile pairs whose bounding boxes lie within ε: the
    join's pruned schedule (integer points, so the f32 slack of its box
    test admits no further pair)."""
    t = x.reshape(N // BP, BP, 3)
    lo, hi = t.min(axis=1), t.max(axis=1)
    g = np.maximum(np.maximum(lo[:, None] - hi[None], lo[None] - hi[:, None]), 0)
    return int(np.tril((g * g).sum(-1) <= EPS * EPS).sum())


def test_count_returns_the_total_and_counters_is_a_copy():
    n0 = counters().get("test.counter", 0)
    assert count("test.counter") == n0 + 1
    assert count("test.counter", 4) == n0 + 5
    snap = counters()
    snap["test.counter"] = -1
    assert counters()["test.counter"] == n0 + 5


def test_join_counters_match_the_inputs(points):
    pt = N // BP
    want_pairs, want_live = _brute(points)
    assert 0 < want_live < pt * (pt + 1) // 2  # some tiles empty, some not
    before = counters()
    out = ops.simjoin_pairs(jnp.asarray(points), EPS, bp=BP)
    d = _delta(before)
    kept = _box_pairs(points)
    assert want_live <= kept < pt * (pt + 1) // 2
    assert out.shape == (want_pairs, 2)
    assert d == {
        "simjoin.joins": 1,
        "simjoin.tri_pairs": pt * (pt + 1) // 2,
        "simjoin.tile_pairs": kept,
        "simjoin.pass1_steps": kept,
        "simjoin.pass1_blocks": 1,
        "simjoin.pass2_blocks": 1,
        "simjoin.pairs_out": want_pairs,
        "simjoin.tiles_live": want_live,
        "simjoin.mask_rows_scanned": 1 << (want_live - 1).bit_length(),
        "simjoin.mask_cells_scanned": (1 << (want_live - 1).bit_length()) * BP * BP,
        "simjoin.rows_flattened": (1 << (want_live - 1).bit_length()) * BP,
    }


@pytest.mark.parametrize("halo", [False, True], ids=["replicated", "halo"])
def test_sharded_join_counts_as_the_single_core_one(points, halo):
    """The sharded join's counters on a one-device mesh: the same pairs,
    live tiles and compaction as one core; its pass 1 visits the whole
    triangle the single core prunes when replicated, and no more than
    it under the halo's reach pruning."""
    from repro.kernels.sharded import simjoin_pairs_sharded
    from repro.launch.mesh import make_app_mesh

    x = jnp.asarray(points)
    before = counters()
    ops.simjoin_pairs(x, EPS, bp=BP)
    single = _delta(before)
    before = counters()
    simjoin_pairs_sharded(x, EPS, mesh=make_app_mesh(1), bp=BP, halo=halo)
    d = _delta(before)
    steps = d.pop("simjoin.tile_pairs")
    tri = single.pop("simjoin.tri_pairs")
    assert single.pop("simjoin.tile_pairs") <= steps <= tri
    if not halo:
        assert steps == (N // BP) * (N // BP + 1) // 2 == tri
    for name in ("simjoin.joins", "simjoin.pass1_steps", "simjoin.pass1_blocks",
                 "simjoin.pass2_blocks"):
        single.pop(name)
    assert d == single


def test_order_cache_counters_agree_with_cache_info(points):
    x = jnp.asarray(points)
    _cached_order.cache_clear()
    before = counters()
    p1 = hilbert_point_order_cached(x)
    p2 = hilbert_point_order_cached(x)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    info = _cached_order.cache_info()
    d = _delta(before)
    assert (info.hits, info.misses) == (1, 1)
    assert d == {"order_cache.hits": 1, "order_cache.misses": 1}
    # clearing resets what the cache reports, not the counters
    _cached_order.cache_clear()
    assert _cached_order.cache_info()[:2] == (0, 0)
    assert counters()["order_cache.misses"] == before.get("order_cache.misses", 0) + 1


def test_join_spans_nest_in_one_trace(points, tmp_path):
    from jax.profiler import ProfileData

    x = jnp.asarray(points)
    jax.block_until_ready(ops.simjoin_pairs(x, EPS, bp=BP, hilbert_order=True))  # compiled
    join = counters()["simjoin.joins"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        jax.block_until_ready(ops.simjoin_pairs(x, EPS, bp=BP, hilbert_order=True))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    (line,) = [ln for ln in lines if any(ev.name == "simjoin.pairs" for ev in ln.events)]
    evs = sorted((ev for ev in line.events if ev.name.startswith("simjoin.")),
                 key=lambda ev: ev.start_ns)
    (outer,) = [ev for ev in evs if ev.name == "simjoin.pairs"]
    assert dict(outer.stats) == {"join": join}
    a, b = outer.start_ns, outer.start_ns + outer.duration_ns
    children = [ev for ev in evs if ev is not outer]
    assert tuple(ev.name for ev in children) == STAGES
    for ev in children:
        assert a <= ev.start_ns and ev.start_ns + ev.duration_ns <= b, ev.name
    ends = [ev.start_ns + ev.duration_ns for ev in children]
    starts = [ev.start_ns for ev in children]
    assert all(e <= s for e, s in zip(ends, starts[1:]))  # one after another

