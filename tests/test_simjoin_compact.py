"""The ε-join's two-level compaction against numpy.

Pass 2 (:func:`simjoin_emit_swizzled`) packs each tile row's hit columns
to the row's first lanes and counts them; :func:`pairs_from_masks`
flattens the packed rows into pairs.  Both are held to numpy: the
kernel's rows to ``np.nonzero`` of the hit mask row by row, the pairs to
the cell-level order the join has always had (table row, then row-major
in the tile).  Points lie on a small integer grid, so every squared
distance is exact and numpy's hit masks are the kernel's.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.simjoin import (
    _id_code,
    emission_table,
    pairs_from_masks,
    simjoin_emit_swizzled,
)

DENSITIES = ["none", "one", "1%", "30%", "all"]
BPS = [32, 100, 256]


def _masks(x, table, bp, eps2, n_valid):
    """numpy hit masks bool[rows, bp, bp] of the ``(i, j, live)`` rows."""
    out = np.zeros((len(table), bp, bp), bool)
    ii = np.arange(bp)[:, None]
    jj = np.arange(bp)[None, :]
    for s, (ti, tj, live) in enumerate(table):
        xi = x[ti * bp:(ti + 1) * bp]
        xj = x[tj * bp:(tj + 1) * bp]
        hit = ((xi[:, None, :] - xj[None, :, :]) ** 2).sum(-1) <= eps2
        hit &= (ii > jj) | (ti != tj)
        hit &= (ti * bp + ii < n_valid) & (tj * bp + jj < n_valid)
        out[s] = hit & bool(live)
    return out


def _eps2(x, table, bp, n_valid, density):
    """A half-integer ε² giving the table's tiles ``density``."""
    d2 = np.concatenate([
        np.ravel(m) for m in _dists(x, table, bp, n_valid)
    ])
    if density == "none":
        return 0.5
    if density == "all":
        return float(d2.max()) + 0.5
    if density == "one":
        return float(d2.min()) + 0.5
    q = {"1%": 0.01, "30%": 0.30}[density]
    return float(np.quantile(d2, q)) + 0.5


def _dists(x, table, bp, n_valid):
    """Squared distances of the valid, counted cells of each live row."""
    ii = np.arange(bp)[:, None]
    jj = np.arange(bp)[None, :]
    for ti, tj, live in table:
        if not live:
            continue
        xi = x[ti * bp:(ti + 1) * bp]
        xj = x[tj * bp:(tj + 1) * bp]
        d2 = ((xi[:, None, :] - xj[None, :, :]) ** 2).sum(-1)
        ok = ((ii > jj) | (ti != tj)) & (ti * bp + ii < n_valid) & (
            tj * bp + jj < n_valid
        )
        yield d2[ok]


def _points(bp, seed):
    """Three tiles of points, the last one ragged (5 pad rows), on a grid
    wide enough that squared distances rarely tie."""
    rng = np.random.default_rng(seed)
    n_valid = 3 * bp - 5
    x = np.zeros((3 * bp, 3), np.int64)
    x[:n_valid] = rng.integers(0, 1 << 10, (n_valid, 3))
    return x, n_valid


# (i, j, live): a diagonal tile, off-diagonal tiles (one ragged), a
# ragged diagonal tile and a dead row; emission_table pads to 8 rows
TILES = np.array([[0, 0], [1, 0], [2, 0], [2, 2], [2, 1], [1, 1], [2, 1]])
LIVE = np.array([1, 1, 1, 1, 1, 1, 0])


@pytest.mark.parametrize("bp", BPS)
@pytest.mark.parametrize("density", DENSITIES)
def test_kernel_packs_each_row_as_nonzero(bp, density):
    x, n_valid = _points(bp, seed=bp)
    table = emission_table(TILES, LIVE)
    assert len(table) == 8  # one dead row in the table, one bucket pad
    eps2 = _eps2(x, table, bp, n_valid, density)
    want = _masks(x, table, bp, eps2, n_valid)
    if density == "one":
        assert want.sum() == 1
    ids, counts = simjoin_emit_swizzled(
        jnp.asarray(table), jnp.asarray(x, jnp.float32), eps=float(np.sqrt(eps2)),
        bp=bp, n_valid=n_valid, interpret=True,
    )
    dtype, bias = _id_code(bp)
    assert ids.shape == (len(table), bp, bp) and ids.dtype == dtype
    assert counts.shape == (len(table), 1, bp)
    ids = np.asarray(ids).astype(np.int64) + bias
    counts = np.asarray(counts)[:, 0]
    np.testing.assert_array_equal(counts, want.sum(axis=2))
    for s in range(len(table)):
        for r in range(bp):
            nz = np.nonzero(want[s, r])[0]
            np.testing.assert_array_equal(ids[s, r, :len(nz)], nz)


def _packed(masks):
    """numpy pass-2 output of hit masks: (ids, counts) as the kernel
    writes them, the lanes past a row's count holding junk."""
    T, bp, _ = masks.shape
    dtype, bias = _id_code(bp)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, bp, (T, bp, bp))
    for t in range(T):
        for r in range(bp):
            nz = np.nonzero(masks[t, r])[0]
            ids[t, r, :len(nz)] = nz
    counts = masks.sum(axis=2).astype(np.int32)[:, None, :]
    return jnp.asarray((ids - bias).astype(dtype)), jnp.asarray(counts)


def _cell_order(masks, rows, tiles, bp):
    """The join's pairs as the cell-level ``nonzero`` gave them: listed
    row by listed row, row-major in the tile."""
    out = [np.zeros((0, 2), np.int64)]
    for row, (ti, tj) in zip(rows, tiles):
        i, j = np.nonzero(masks[row])
        out.append(np.stack([ti * bp + i, tj * bp + j], axis=1))
    return np.concatenate(out)


@pytest.mark.parametrize("bp", BPS)
@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("form", ["single", "sharded"])
def test_pairs_from_masks_keeps_the_cell_order(bp, density, form):
    rng = np.random.default_rng(bp)
    T = 12
    p = {"none": 0.0, "one": 0.0, "1%": 0.01, "30%": 0.30, "all": 1.0}[density]
    masks = rng.uniform(size=(T, bp, bp)) < p
    if density == "one":
        masks[5, bp // 2, bp // 3] = True
    masks[T - 1] = False  # a dead row, as pass 2 writes it
    if form == "single":
        # the single-chip driver: every non-empty row, in table order
        rows = np.arange(T - 1)
    else:
        # the sharded drivers: a selection across shards, out of order,
        # that skips rows and reads the id table in place
        rows = np.array([6, 0, 9, 3, 10, 1, 5])
    tiles = rng.integers(0, 40, (len(rows), 2))
    want = _cell_order(masks, rows, tiles, bp)
    ids, counts = _packed(masks)
    got = pairs_from_masks(ids, counts, rows, tiles, len(want), bp)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
