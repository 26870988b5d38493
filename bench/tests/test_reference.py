"""The ε-join's reference, blocked into lower-triangle tiles, counts and
lists exactly what the reference that spans whole rows did, on integer
grids (the configurations' domain) with ragged sizes, several tile
shapes and ε = 0."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import simjoin as ref
from bench.reference.kmeans import dot


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk"))
def _row_count(x, *, eps2, precision, chunk):
    """The reference before blocking: (chunk, N) rows at a time."""
    n = x.shape[0]
    chunk = min(chunk, n)
    xp = jnp.pad(x, ((0, (-n) % chunk), (0, 0)))
    xn = jnp.sum(x * x, axis=1)

    def body(s, tot):
        xi = jax.lax.dynamic_slice_in_dim(xp, s * chunk, chunk)
        d2 = jnp.sum(xi * xi, axis=1)[:, None] - 2.0 * dot(xi, x.T, precision) + xn[None, :]
        i = s * chunk + jnp.arange(chunk)[:, None]
        j = jnp.arange(n)[None, :]
        return tot + jnp.sum((d2 <= eps2) & (j < i) & (i < n), dtype=jnp.int32)

    return jax.lax.fori_loop(0, xp.shape[0] // chunk, body, jnp.int32(0))


def _grid(n, side, seed):
    return jnp.asarray(np.random.default_rng(seed).integers(0, side, (n, 3)), jnp.float32)


CASES = [
    # n, grid side, eps², chunk, block
    (1000, 64, 30.5, 64, 256),
    (1001, 64, 30.5, 64, 256),  # one row past the last chunk
    (777, 40, 12.5, 100, 300),  # block not a multiple of the chunk
    (513, 16, 0.0, 32, 64),  # ε = 0: duplicate points only
    (300, 128, 2000.5, 1024, 16384),  # one tile wider than the set
    (2048, 2048, 2257.5, 1024, 16384),  # the cells' grid and ε (eps-k6), default tiles
]


@pytest.mark.parametrize("n,side,eps2,chunk,block", CASES)
def test_blocked_count_equals_the_row_count(n, side, eps2, chunk, block):
    x = _grid(n, side, n)
    want = int(_row_count(x, eps2=eps2, precision="highest", chunk=1024))
    assert ref.pair_count(x, eps2, chunk=chunk, block=block) == want
    if eps2 == 0.0:
        assert want > 0  # the grid is small enough to repeat points


@pytest.mark.parametrize("n,side,eps2,chunk,block", CASES[:4])
def test_blocked_pairs_are_the_exact_set(n, side, eps2, chunk, block):
    x = _grid(n, side, n)
    got = np.asarray(ref.pairs(x, eps2, chunk=chunk, block=block))
    xs = np.asarray(x, np.int64)
    d2 = ((xs[:, None, :] - xs[None, :, :]) ** 2).sum(-1)
    i, j = np.nonzero(np.tril(d2 <= eps2, k=-1))
    assert sorted(map(tuple, got)) == sorted(zip(i, j))
    assert ref.compare(x, got, len(i), eps2)["mismatched"] == 0


def test_control_count_at_high_precision():
    """The three-pass control goes through the same tiles."""
    x = _grid(1001, 2048, 5)
    want = int(_row_count(x, eps2=20000.5, precision="high", chunk=1024))
    assert ref.pair_count(x, 20000.5, precision="high", chunk=64, block=256) == want
    assert len(ref.pairs(x, 20000.5, precision="high", chunk=64, block=256)) == want
