"""The ε-join's two-level compaction against numpy and the XLA flatten.

Pass 2 (:func:`simjoin_emit_swizzled`) packs each tile row's hit columns
to the row's first lanes and counts them; the flatten kernel
(:func:`simjoin_compact`, under :func:`pairs_from_masks`) writes the
packed rows out as pairs.  The kernel's rows are held to ``np.nonzero``
of the hit mask row by row, the pairs to the cell-level order the join
has always had (table row, then row-major in the tile), and the flatten
bit for bit to the XLA row flatten it replaced (:func:`_flatten_rows`
below, the oracle).  Points lie on a small integer grid, so every
squared distance is exact and numpy's hit masks are the kernel's.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.simjoin import (
    _flatten_widths,
    _id_code,
    _spread,
    emission_table,
    pairs_from_masks,
    simjoin_compact,
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


@functools.partial(jax.jit, static_argnames=("bp", "P"))
def _flatten_rows(ids, counts, rows, tiles, *, bp: int, P: int):
    """The oracle: the XLA row flatten the kernel replaced.  The first
    ``P`` hits of the packed rows of table rows ``rows`` (-1: padding) as
    global ids ``(i, j)``, two int32[P], walking tile rows, never cells.

    Tile row q (row ``q % bp`` of listed table row ``t = q // bp``) holds
    outputs ``[off_q, off_q + n_q)``, ``off`` the exclusive cumsum of the
    row counts; output k is its hit ``p = k - off_q``.  One spread over
    rows gives every output ``w = q·bp + p``; one over table rows gives
    its tile's global bases and how far its ids lie from slot t in the
    id table, which ``rows`` indexes in place.  The column id is then
    one 1-D gather."""
    _, bias = _id_code(bp)
    n = rows.shape[0]
    live = rows >= 0
    rows = jnp.where(live, rows, 0)
    cnt = jnp.where(live[:, None], counts.reshape(-1, bp)[rows], 0).reshape(-1)
    off = jnp.cumsum(cnt) - cnt
    q = jnp.arange(n * bp, dtype=jnp.int32)
    w = _spread(off, q * bp - off, P) + jnp.arange(P, dtype=jnp.int32)
    gi, gj, moved = _spread(off[::bp], jnp.stack([
        tiles[:, 0] * bp,
        tiles[:, 1] * bp,
        (rows - jnp.arange(n, dtype=jnp.int32)) * (bp * bp),
    ]), P)
    col = ids.reshape(-1)[w + moved].astype(jnp.int32) + bias
    return gi + w // bp % bp, gj + col


# hits per listed tile for "edges": runs that end on a 128-lane edge
# (at 128, 256, 640 and 896), empty tiles, runs that start on an edge or
# inside the carried row and cross one or more rows, and single hits
EDGE_HITS = [128, 0, 100, 28, 300, 1, 83, 129, 1, 126, 0]
CASES = ["none", "one", "1%", "30%", "all", "diagonal", "edges"]


def _case_masks(bp, case, T=12):
    """bool[T, bp, bp] hit masks of one case; slot ``T - 1`` is dead."""
    rng = np.random.default_rng([bp, CASES.index(case)])
    if case == "edges":
        masks = np.zeros((T, bp, bp), bool)
        for t, k in enumerate(EDGE_HITS[: T - 1]):
            masks[t].flat[rng.choice(bp * bp, min(k, bp * bp), replace=False)] = True
    elif case == "diagonal":
        # a full diagonal tile (each pair once), a full tile, and 10% ones
        masks = rng.uniform(size=(T, bp, bp)) < 0.1
        masks[2] = np.tri(bp, k=-1, dtype=bool)
        masks[7] = True
    else:
        p = {"none": 0.0, "one": 0.0, "1%": 0.01, "30%": 0.30, "all": 1.0}[case]
        masks = rng.uniform(size=(T, bp, bp)) < p
        if case == "one":
            masks[5, bp // 2, bp // 3] = True
    masks[T - 1] = False  # a dead row, as pass 2 writes it
    return masks


FORMS = {
    # the single-chip join: every row, in table order
    "single": np.arange(11),
    # the sharded joins: a selection across shards, out of order, that
    # skips rows and reads the id table in place
    "sharded": np.array([6, 0, 9, 3, 10, 1, 5]),
}


@pytest.mark.parametrize("bp", BPS)
def test_every_run_width_is_exercised(bp):
    # the kernel lays a tile out at the narrowest run width that holds
    # its fullest row; the cases below reach every width it compiles
    _, _, widths = _flatten_widths(bp)
    used = set()
    for case in CASES:
        fullest = _case_masks(bp, case).sum(axis=2).max(axis=1)
        used |= {int(np.searchsorted(widths, m)) for m in fullest}
    assert used == set(range(len(widths)))


@pytest.mark.parametrize("bp", BPS)
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("form", sorted(FORMS))
def test_flatten_kernel_matches_the_xla_flatten(bp, case, form):
    masks = _case_masks(bp, case)
    rows = FORMS[form]
    tiles = np.random.default_rng(bp).integers(0, 40, (len(rows), 2))
    ids, counts = _packed(masks)
    # one compiled shape per bp: 16 listed rows (padding -1), one cap
    cap = 1 << (12 * bp * bp - 1).bit_length()
    rows_p = np.full(16, -1, np.int32)
    rows_p[: len(rows)] = rows
    tiles_p = np.zeros((16, 2), np.int32)
    tiles_p[: len(rows)] = tiles
    P = int(masks[rows].sum())
    if case == "edges" and form == "single":
        ends = np.cumsum(masks[rows].sum(axis=(1, 2)))
        assert {128, 256, 640, 896} <= set(ends.tolist())  # runs end on edges
    out = simjoin_compact(
        jnp.zeros((cap, 2), jnp.int32), ids, counts, jnp.asarray(rows_p),
        jnp.asarray(tiles_p), None, 0, bp=bp, cap=cap, interpret=True,
    )
    i, j = _flatten_rows(
        ids, counts, jnp.asarray(rows_p), jnp.asarray(tiles_p), bp=bp, P=cap
    )
    want = np.stack([np.asarray(i), np.asarray(j)], axis=1)[:P]
    np.testing.assert_array_equal(np.asarray(out)[:P], want)
    np.testing.assert_array_equal(want, _cell_order(masks, rows, tiles, bp))
