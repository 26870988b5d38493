"""Plain Lloyd k-means and the numbers that compare a fit with it.

Nothing here imports the program.  The reference restates Lloyd's
algorithm in a few lines of ``jax.numpy``: assign every point to its
nearest centroid (smallest index on a tie), move every non-empty
centroid to the mean of its points, keep an empty one where it was.
It starts from the same initial centroids as the fit under test: the
rule is stated by the entry's contract (K distinct points drawn by
``jax.random.choice`` from the fit's seed), restated here from that
contract, not taken from the program.

The distance is ||c||² − 2·x·c (||x||² does not change the argmin),
x·c one matrix product at ``precision``: ``"highest"`` is exact float32,
the precision the configuration states; ``"high"`` (three bf16 passes,
the low parts' product dropped) is the control, the nearest precision
below it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def init_centroids(x, k: int, seed: int):
    n = x.shape[0]
    idx = jax.random.choice(jax.random.PRNGKey(seed), n, shape=(k,), replace=k > n)
    return x[idx]


def _bf16_split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def dot(a, b, precision: str):
    """a @ b with float32 products (``"highest"``) or with the three-pass
    bf16 products of ``"high"``, written out so that they are the same on
    every backend: both operands split into bf16 high and low parts,
    the low parts' product dropped."""
    if precision == "highest":
        return jnp.dot(a, b, precision="highest")
    if precision != "high":
        raise ValueError(f"precision {precision!r}")
    ah, al = _bf16_split(a)
    bh, bl = _bf16_split(b)
    hi = functools.partial(jnp.dot, precision="highest")
    return hi(ah, bh) + (hi(ah, bl) + hi(al, bh))


@functools.partial(jax.jit, static_argnames=("precision", "chunk"))
def assign(x, c, *, precision: str = "highest", chunk: int = 8192):
    """Index of the nearest centroid of every point (smallest on a tie)."""
    n, d = x.shape
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    cn = jnp.sum(c * c, axis=1)

    def one(xc):
        m = cn[None, :] - 2.0 * dot(xc, c.T, precision)
        return jnp.argmin(m, axis=1).astype(jnp.int32)

    return jax.lax.map(one, xp.reshape(-1, chunk, d)).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("k",))
def means(x, a, c_prev, *, k: int):
    """Mean of each centroid's points; an empty centroid keeps ``c_prev``.
    Also returns the counts."""
    s = jax.ops.segment_sum(x, a, num_segments=k)
    cnt = jax.ops.segment_sum(jnp.ones(a.shape, jnp.float32), a, num_segments=k)
    return jnp.where(cnt[:, None] > 0, s / jnp.maximum(cnt, 1.0)[:, None], c_prev), cnt


def lloyd(x, k: int, iters: int, seed: int, *, precision="highest"):
    """(centroids, last assignment) after ``iters`` Lloyd iterations."""
    c = init_centroids(x, k, seed)
    a = None
    for _ in range(iters):
        a = assign(x, c, precision=precision)
        c, _ = means(x, a, c, k=k)
    return c, a


def compare(x, got_c, got_a, want_c, want_a) -> dict:
    """The numbers one fit is judged by.

    * ``assign_mismatch`` — share of points whose final assignment
      differs from the reference's;
    * ``centroid_gap`` — largest coordinate gap between the two sets of
      centroids, over the largest reference coordinate;
    * ``update_gap`` — largest coordinate gap between each non-empty
      centroid the fit returned and the mean of the points the fit
      itself assigned to it, over the largest reference coordinate (it
      reads a fit's update step alone, whatever path the iterations
      took).
    """
    k = want_c.shape[0]
    scale = jnp.max(jnp.abs(want_c))
    mean_c, cnt = means(x, got_a, got_c, k=k)
    live = (cnt > 0)[:, None]
    return {
        "assign_mismatch": float(jnp.mean((got_a != want_a).astype(jnp.float32))),
        "centroid_gap": float(jnp.max(jnp.abs(got_c - want_c)) / scale),
        "update_gap": float(jnp.max(jnp.where(live, jnp.abs(got_c - mean_c), 0.0)) / scale),
    }
