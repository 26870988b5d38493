"""Lloyd k-means fits through ``ops.kmeans_lloyd``, one job at a time.

The configuration gives the data set (points, features, the mixture
that stands in for the data); the traffic gives the job (K, the
iterations, how many fits the check samples, the limits).  Each fit of
a run clusters the same resident points from a new initialisation
seed, as a batch-job runner that restarts k-means does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import kmeans as ref
from bench.work import kmeans as work_count


@functools.partial(jax.jit, static_argnames=("n", "d", "components"))
def mixture(key, spread, sigma, *, n: int, d: int, components: int):
    """``n`` points of a Gaussian mixture in ``d`` features, centred on
    the origin: component centres uniform in [-spread/2, spread/2)^d,
    isotropic noise ``sigma``.  One call, on the device."""
    kc, ka, kn = jax.random.split(key, 3)
    centres = (jax.random.uniform(kc, (components, d), jnp.float32) - 0.5) * spread
    which = jax.random.randint(ka, (n,), 0, components)
    return centres[which] + sigma * jax.random.normal(kn, (n, d), jnp.float32)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seeds):
        self.n, self.d = int(cfg["n_points"]), int(cfg["n_features"])
        self.k, self.iters = int(traffic["k"]), int(traffic["iters"])
        self.traffic = traffic
        # tile sizes the job passes to the entry, where it must (bench/traffic)
        self.blocks = {b: int(traffic[b]) for b in ("bp", "bc") if b in traffic}
        mix = cfg["mixture"]
        self.x = mixture(seeds.key("data"), float(mix["spread"]), float(mix["sigma"]),
                         n=self.n, d=self.d, components=int(mix["components"]))
        jax.block_until_ready(self.x)
        self.seeds = seeds

    def job(self, i):
        """The fit's initialisation seed (``i`` is the solve's index,
        ``"warmup"`` for the one set-up fit)."""
        return self.seeds.int31("job", i)

    def solve(self, seed):
        from repro.kernels import ops

        return ops.kmeans_lloyd(self.x, self.k, iters=self.iters, seed=seed, **self.blocks)

    def size(self, out):
        """Output size of one fit, as the work count needs it: none."""
        return None

    def work(self, sizes) -> dict:
        """Least work of one fit."""
        return work_count.work(self.n, self.d, self.k, self.iters)

    def check(self, seed, out) -> dict:
        """The numbers that compare one fit with the reference's."""
        c, a = out
        want_c, want_a = ref.lloyd(self.x, self.k, self.iters, seed)
        return ref.compare(self.x, c, a, want_c, want_a)
