"""Plain dense ε-self-join and the numbers that compare a join with it.

Nothing here imports the program.  The reference visits every pair
(i, j), i > j, in tiles of a row chunk by a column block that hold a
pair of the lower triangle, so that its peak memory is a few tiles at
any N; it forms the squared distance
||x_i||² − 2·x_i·x_j + ||x_j||² with the product at ``precision``
(``bench.reference.kmeans.dot``), and
keeps the pair when it is at most ε².  On the integer-grid points of
the benchmark's configurations every term is an exact float32 at
``"highest"``, the precision the configuration states, so the pair set
is exact; ``"high"`` (three bf16 passes) is the control.

A join is judged by the size of the symmetric difference between its
pair set and the reference's, computed without materialising the
reference's pairs: each pair the join emitted is checked on its own
(canonical, in range, within ε, not repeated), and the reference
supplies only its exact pair count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.kmeans import dot


# rows per chunk and columns per block of a tile: 64 MB of f32 distances
# at most, so a count's peak memory is bounded at any N
CHUNK, BLOCK = 1024, 16384


def _layout(n: int, chunk: int, block: int):
    """Rows per chunk ``r``, columns per block ``c`` (a multiple of ``r``)
    and the padded point count (a multiple of ``c``)."""
    r = min(chunk, n)
    c = min(max(block // r, 1) * r, -(-n // r) * r)
    return r, c, -(-n // c) * c


def _hits(xp, xn, lo, co, r: int, c: int, n: int, eps2: float, precision: str):
    """(r, c) mask of the pairs (lo + a, co + b) with co + b < lo + a < n,
    within ε, of the padded points ``xp`` and their squared norms ``xn``."""
    xi = jax.lax.dynamic_slice_in_dim(xp, lo, r)
    xj = jax.lax.dynamic_slice_in_dim(xp, co, c)
    d2 = (
        jax.lax.dynamic_slice_in_dim(xn, lo, r)[:, None]
        - 2.0 * dot(xi, xj.T, precision)
        + jax.lax.dynamic_slice_in_dim(xn, co, c)[None, :]
    )
    i = lo + jnp.arange(r)[:, None]
    j = co + jnp.arange(c)[None, :]
    return (d2 <= eps2) & (j < i) & (i < n)


def _padded(x, n_pad: int):
    xp = jnp.pad(x, ((0, n_pad - x.shape[0]), (0, 0)))
    return xp, jnp.sum(xp * xp, axis=1)


def _blocks(lo, r: int, c: int):
    """Column blocks that hold a column left of row chunk ``lo``'s last row."""
    return (lo + r - 2) // c + 1


def _chunk_total(xp, xn, lo, r: int, c: int, n: int, eps2: float, precision: str):
    """Pairs of row chunk ``lo``, over the column blocks left of its last row."""
    def col(b, tot):
        h = _hits(xp, xn, lo, b * c, r, c, n, eps2, precision)
        return tot + jnp.sum(h, dtype=jnp.int32)

    return jax.lax.fori_loop(0, _blocks(lo, r, c), col, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk", "block"))
def _count(x, *, eps2: float, precision: str, chunk: int, block: int):
    n = x.shape[0]
    r, c, n_pad = _layout(n, chunk, block)
    xp, xn = _padded(x, n_pad)

    def row(s, tot):
        return tot + _chunk_total(xp, xn, s * r, r, c, n, eps2, precision)

    return jax.lax.fori_loop(0, -(-n // r), row, jnp.int32(0))


def pair_count(x, eps2: float, *, precision: str = "highest", chunk: int = CHUNK,
               block: int = BLOCK) -> int:
    """Number of unordered pairs within ε, counted over (chunk × block)
    tiles of the lower triangle."""
    return int(_count(x, eps2=float(eps2), precision=precision, chunk=chunk, block=block))


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk", "block"))
def _chunk_count(x, lo, *, eps2, precision, chunk, block):
    n = x.shape[0]
    r, c, n_pad = _layout(n, chunk, block)
    xp, xn = _padded(x, n_pad)
    return _chunk_total(xp, xn, lo, r, c, n, eps2, precision)


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk", "block", "size"))
def _chunk_pairs(x, lo, *, eps2, precision, chunk, block, size):
    """Row chunk ``lo``'s pairs, block after block, in the first of
    ``2 * size`` rows (``size`` at least the chunk's pair count): each
    block writes ``size`` rows where the last one's pairs end."""
    n = x.shape[0]
    r, c, n_pad = _layout(n, chunk, block)
    xp, xn = _padded(x, n_pad)

    def col(b, carry):
        out, at = carry
        h = _hits(xp, xn, lo, b * c, r, c, n, eps2, precision)
        a, j = jnp.nonzero(h, size=size, fill_value=0)
        p = jnp.stack([lo + a, b * c + j], axis=1).astype(jnp.int32)
        out = jax.lax.dynamic_update_slice_in_dim(out, p, at, axis=0)
        return out, at + jnp.sum(h, dtype=jnp.int32)

    out = jnp.zeros((2 * size, 2), jnp.int32)
    return jax.lax.fori_loop(0, _blocks(lo, r, c), col, (out, jnp.int32(0)))[0]


def pairs(x, eps2: float, *, precision: str = "highest", chunk: int = CHUNK,
          block: int = BLOCK):
    """The pair list itself, int32[P, 2] with i > j (the control's output),
    found over the same tiles as :func:`pair_count`."""
    n = x.shape[0]
    r = _layout(n, chunk, block)[0]
    kw = {"eps2": float(eps2), "precision": precision, "chunk": chunk, "block": block}
    out = []
    for lo in range(0, n, r):
        m = int(_chunk_count(x, lo, **kw))
        if m:
            size = 1 << (m - 1).bit_length()
            out.append(_chunk_pairs(x, lo, size=size, **kw)[:m])
    if not out:
        return jnp.zeros((0, 2), jnp.int32)
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("eps2",))
def _judge(x, p, *, eps2: float):
    n = x.shape[0]
    i, j = p[:, 0], p[:, 1]
    ok = (i > j) & (j >= 0) & (i < n)
    ic, jc = jnp.clip(i, 0, n - 1), jnp.clip(j, 0, n - 1)
    diff = x[ic] - x[jc]
    ok = ok & (jnp.sum(diff * diff, axis=1) <= eps2)
    order = jnp.lexsort((jc, ic))
    si, sj, sok = ic[order], jc[order], ok[order]
    rep = (si[1:] == si[:-1]) & (sj[1:] == sj[:-1]) & sok[1:] & sok[:-1]
    return jnp.sum(~ok, dtype=jnp.int32), jnp.sum(rep, dtype=jnp.int32)


def compare(x, got_pairs, want_count: int, eps2: float) -> dict:
    """Sizes of the difference between a join's pairs and the exact set.

    ``x`` are the points the pairs index (``got_pairs`` int[P, 2],
    i > j), ``want_count`` the reference's pair count.  ``invalid``
    pairs are out of range, not canonical or farther than ε;
    ``repeated`` ones appear more than once; ``missing`` is the number
    of reference pairs the join did not emit.  ``mismatched`` is their
    sum: the size of the symmetric difference.
    """
    p = jnp.asarray(got_pairs, jnp.int32).reshape(-1, 2)
    if p.shape[0] == 0:
        bad = rep = 0
    else:
        bad, rep = (int(v) for v in _judge(x, p, eps2=float(eps2)))
    missing = int(want_count) - (int(p.shape[0]) - bad - rep)
    return {
        "invalid": bad, "repeated": rep, "missing": missing,
        "mismatched": bad + rep + abs(missing),
    }
