"""Plain dense ε-self-join and the numbers that compare a join with it.

Nothing here imports the program.  The reference visits every pair
(i, j), i > j, in row chunks, forms the squared distance
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


def _chunks(x, chunk: int):
    n = x.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    return jnp.pad(x, ((0, pad), (0, 0))), chunk


def _hits(x, xp, lo, chunk: int, eps2: float, precision: str):
    """(chunk, N) mask of the pairs (lo + r, j) with j < lo + r, within ε."""
    n = x.shape[0]
    xi = jax.lax.dynamic_slice_in_dim(xp, lo, chunk)
    xn = jnp.sum(x * x, axis=1)
    d2 = (
        jnp.sum(xi * xi, axis=1)[:, None]
        - 2.0 * dot(xi, x.T, precision)
        + xn[None, :]
    )
    i = lo + jnp.arange(chunk)[:, None]
    j = jnp.arange(n)[None, :]
    return (d2 <= eps2) & (j < i) & (i < n)


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk"))
def _count(x, *, eps2: float, precision: str, chunk: int):
    xp, chunk = _chunks(x, chunk)

    def body(s, tot):
        h = _hits(x, xp, s * chunk, chunk, eps2, precision)
        return tot + jnp.sum(h, dtype=jnp.int32)

    return jax.lax.fori_loop(0, xp.shape[0] // chunk, body, jnp.int32(0))


def pair_count(x, eps2: float, *, precision: str = "highest", chunk: int = 1024) -> int:
    """Number of unordered pairs within ε."""
    return int(_count(x, eps2=float(eps2), precision=precision, chunk=chunk))


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk"))
def _chunk_count(x, lo, *, eps2, precision, chunk):
    xp, chunk = _chunks(x, chunk)
    return jnp.sum(_hits(x, xp, lo, chunk, eps2, precision), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("eps2", "precision", "chunk", "size"))
def _chunk_pairs(x, lo, *, eps2, precision, chunk, size):
    xp, chunk = _chunks(x, chunk)
    h = _hits(x, xp, lo, chunk, eps2, precision)
    r, j = jnp.nonzero(h, size=size, fill_value=0)
    return jnp.stack([lo + r, j], axis=1).astype(jnp.int32)


def pairs(x, eps2: float, *, precision: str = "highest", chunk: int = 1024):
    """The pair list itself, int32[P, 2] with i > j (the control's output)."""
    n_pad = _chunks(x, chunk)[0].shape[0]
    chunk = min(chunk, x.shape[0])
    out = []
    for lo in range(0, n_pad, chunk):
        m = int(_chunk_count(x, lo, eps2=float(eps2), precision=precision, chunk=chunk))
        if m:
            size = 1 << (m - 1).bit_length()
            out.append(_chunk_pairs(x, lo, eps2=float(eps2), precision=precision,
                                    chunk=chunk, size=size)[:m])
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
