"""ε-self-joins through ``ops.simjoin_pairs``, one job at a time.

The configuration gives the point set (count, dimensions, the integer
grid's side, and the fixed draw ``data_seed``); the traffic gives ε as
a mean neighbour count, how many joins the check samples and the
limits.  A run's seed turns that set by one of the grid cube's 48
symmetries, and each join gets the turned set in a new order, drawn
from the seed and the join's index.  So every join computes the Hilbert
order anew (its cache is keyed on the order of the points and misses),
while the pair count, and with it every shape, is the same in every run
of the cell: the entry's glue compiles once per output size (a gather
in ``map_pairs_back`` took 663 s to compile on the chip), and so only
the first run on a machine compiles, and nothing compiles in a window.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import simjoin as ref
from bench.work import simjoin as work_count


@functools.partial(jax.jit, static_argnames=("n", "d", "side"))
def grid_points(key, *, n: int, d: int, side: int):
    """Uniform points on the integer grid [0, side)^d: every squared
    distance between them is an exact float32 while d·side² < 2^24."""
    return jax.random.randint(key, (n, d), 0, side).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("side",))
def orient(key, x, *, side: int):
    """One of the grid cube's symmetries of ``x``, drawn from ``key``:
    the axes permuted, each reflected or not.  Distances, and so the
    pair count, are those of ``x``; the Hilbert order is another."""
    ka, kf = jax.random.split(key)
    axes = jax.random.permutation(ka, x.shape[1])
    flip = jax.random.bernoulli(kf, 0.5, (x.shape[1],))
    y = x[:, axes]
    return jnp.where(flip[None, :], (side - 1) - y, y)


@functools.partial(jax.jit, static_argnames=("n",))
def reorder(key, x, *, n: int):
    return x[jax.random.permutation(key, n)]


def eps_squared(n: int, d: int, side: int, neighbours: float) -> float:
    """ε² for about ``neighbours`` neighbours per point in the unit-cube
    formula (d = 3: ε = (k / (N·4π/3))^(1/3)), scaled to the grid's side
    and set half-way between two integers, so no squared distance lies
    on it."""
    if d != 3:
        raise ValueError("the neighbour formula is the 3-D ball's")
    r = side * (neighbours / (n * 4.0 * math.pi / 3.0)) ** (1.0 / 3.0)
    return math.floor(r * r) + 0.5


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seeds):
        self.n, self.d, self.side = int(cfg["n_points"]), int(cfg["n_dims"]), int(cfg["grid_side"])
        if self.d * self.side * self.side >= 2**24:
            raise ValueError("grid too wide for exact float32 squared distances")
        self.traffic = traffic
        self.eps2 = eps_squared(self.n, self.d, self.side, float(traffic["neighbours"]))
        self.eps = math.sqrt(self.eps2)
        fixed = jax.random.key(int(cfg["data_seed"]))
        points = grid_points(fixed, n=self.n, d=self.d, side=self.side)
        self.base = orient(seeds.key("data"), points, side=self.side)
        jax.block_until_ready(self.base)
        self.seeds = seeds
        self._want = None  # the reference's pair count, once per run

    def job(self, i):
        """The join's points: the run's set in the order drawn for ``i``."""
        return reorder(self.seeds.key("job", i), self.base, n=self.n)

    def solve(self, x):
        from repro.kernels import ops

        return ops.simjoin_pairs(x, self.eps, hilbert_order=True)

    def size(self, out):
        """Pairs one join emitted."""
        return int(out.shape[0])

    def work(self, sizes) -> dict:
        """Least work of one join, at the mean pair count of ``sizes``."""
        return work_count.work(self.n, self.d, sum(sizes) / max(len(sizes), 1))

    def check(self, x, pairs) -> dict:
        """The numbers that compare one join with the reference's."""
        if self._want is None:
            self._want = ref.pair_count(self.base, self.eps2)
        return ref.compare(x, pairs, self._want, self.eps2)
