"""The Hilbert point order's cache keys on a digest of the quantised
grid, computed on the device: the same grid hits, any other misses."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kmeans import (
    _cached_order,
    _quantise_points,
    grid_digest,
    hilbert_point_order,
    hilbert_point_order_cached,
)

X = np.random.default_rng(7).integers(0, 256, (1000, 3)).astype(np.float32)


def _digest(q):
    return np.asarray(grid_digest(jnp.asarray(q))).tobytes()


@pytest.mark.parametrize("change", ["one_bit", "two_rows_swapped", "last_cell"])
def test_a_changed_grid_changes_the_digest(change):
    q = np.asarray(_quantise_points(jnp.asarray(X))[0])
    r = q.copy()
    if change == "one_bit":
        r[500, 1] ^= 1
    elif change == "two_rows_swapped":
        a, b = 3, int(np.flatnonzero((q != q[3]).any(axis=1))[0])
        r[[a, b]] = r[[b, a]]
    else:
        r[-1, 2] = (r[-1, 2] + 1) % 256
    assert not np.array_equal(q, r)
    assert _digest(q) == _digest(q.copy()) != _digest(r)
    assert len(_digest(q)) == 16


def test_the_cache_hits_on_the_same_grid_and_misses_on_another():
    _cached_order.cache_clear()
    for x in (X, X.copy(), X[::-1].copy()):
        got = hilbert_point_order_cached(jnp.asarray(x))
        np.testing.assert_array_equal(got, hilbert_point_order(jnp.asarray(x)))
    info = _cached_order.cache_info()
    assert (info.hits, info.misses) == (1, 2)
    _cached_order.cache_clear()
