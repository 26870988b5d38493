"""Fused data-mining apps (PR 4): the kmeans phased schedule, the
single-dispatch fused Lloyd pipeline vs its retained multi-dispatch
reference (bit-identical in interpret mode), and two-pass ε-join pair
emission vs the dense O(N²) oracle.

All kernels run in interpret mode (CPU container; TPU is the target).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KMEANS_PHASES, kmeans_schedule, tile_schedule
from repro.kernels import ops, ref
from repro.kernels.kmeans import (
    kmeans_assign_swizzled,
    kmeans_lloyd_fused,
    kmeans_update_swizzled,
)
from repro.kernels.pallas_compat import PallasCallCounter
from repro.kernels.simjoin import (
    simjoin_compact,
    simjoin_emit_swizzled,
    simjoin_tile_hits_swizzled,
)

RNG = np.random.default_rng(99)


def sorted_pairs(p) -> np.ndarray:
    p = np.asarray(p)
    if len(p) == 0:
        return p.reshape(0, 2)
    return p[np.lexsort((p[:, 1], p[:, 0]))]


# ---------------------------------------------------------------------------
# kmeans phased schedule
# ---------------------------------------------------------------------------

class TestKmeansSchedule:
    @pytest.mark.parametrize("curve", ["row", "fur", "hilbert"])
    @pytest.mark.parametrize("pt,ct", [(1, 1), (4, 2), (5, 3), (8, 8)])
    def test_structure(self, curve, pt, ct):
        s = kmeans_schedule(curve, pt, ct)
        assert s.shape == (pt * ct + pt, 4)
        assert len(KMEANS_PHASES) == 2
        a = s[s[:, 0] == 0]
        u = s[s[:, 0] == 1]
        # phase 0 IS the curve's own (i, j) order
        np.testing.assert_array_equal(a[:, 1:3], tile_schedule(curve, pt, ct))
        # its flag column marks the first visit of each point tile
        assert int(a[:, 3].sum()) == pt
        first_rows = a[a[:, 3] == 1]
        assert len(np.unique(first_rows[:, 1])) == pt
        # phase 1: every point tile exactly once, in phase-0
        # first-appearance order, flag only on its first row
        assert len(u) == pt
        np.testing.assert_array_equal(np.sort(u[:, 1]), np.arange(pt))
        np.testing.assert_array_equal(u[:, 1], first_rows[:, 1])
        np.testing.assert_array_equal(u[:, 3], np.eye(1, pt, 0, dtype=np.int32)[0])
        # phases appear in order (the phase-barrier invariant)
        assert (np.diff(s[:, 0]) >= 0).all()

    def test_cached_and_readonly(self):
        s1 = kmeans_schedule("hilbert", 4, 4)
        s2 = kmeans_schedule("hilbert", 4, 4)
        assert s1 is s2 and not s1.flags.writeable

    def test_empty(self):
        assert kmeans_schedule("row", 0, 3).shape == (0, 4)


# ---------------------------------------------------------------------------
# Fused Lloyd: bit-exact differential + dispatch counts
# ---------------------------------------------------------------------------

class TestFusedLloyd:
    @pytest.mark.parametrize("curve", ["row", "fur", "hilbert"])
    @pytest.mark.parametrize("hilbert_order", [False, True])
    def test_bit_identical_to_reference(self, curve, hilbert_order):
        x = jnp.asarray(RNG.normal(size=(192, 5)), jnp.float32)
        kw = dict(iters=4, curve=curve, bp=64, bc=8,
                  hilbert_order=hilbert_order, interpret=True)
        cf, af = ops.kmeans_lloyd(x, 16, fused=True, **kw)
        cr, ar = ops.kmeans_lloyd(x, 16, fused=False, **kw)
        np.testing.assert_array_equal(np.asarray(cf), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(af), np.asarray(ar))

    def test_randomized_shapes_differential(self):
        for _ in range(5):
            N = int(RNG.integers(5, 200))
            D = int(RNG.integers(1, 9))
            k = int(RNG.integers(1, min(N, 20) + 1))
            bp = int(RNG.choice([8, 32, 64]))
            bc = int(RNG.choice([4, 8, 16]))
            curve = str(RNG.choice(["row", "fur", "hilbert"]))
            ho = bool(RNG.integers(0, 2))
            x = jnp.asarray(RNG.normal(size=(N, D)), jnp.float32)
            kw = dict(iters=3, curve=curve, bp=bp, bc=bc, hilbert_order=ho,
                      interpret=True)
            cf, af = ops.kmeans_lloyd(x, k, fused=True, **kw)
            cr, ar = ops.kmeans_lloyd(x, k, fused=False, **kw)
            ctx = (N, D, k, bp, bc, curve, ho)
            np.testing.assert_array_equal(np.asarray(cf), np.asarray(cr), err_msg=str(ctx))
            np.testing.assert_array_equal(np.asarray(af), np.asarray(ar), err_msg=str(ctx))

    def test_assignment_matches_dense_oracle(self):
        # the assignment returned with iteration t's centroids is the
        # dense nearest-centroid rule applied to the (t-1)-updated c
        x = jnp.asarray(RNG.normal(size=(150, 4)), jnp.float32)
        c_prev, _ = ops.kmeans_lloyd(x, 6, iters=2, bp=32, bc=4, interpret=True)
        _, a = ops.kmeans_lloyd(x, 6, iters=3, bp=32, bc=4, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(ref.kmeans_assign(x, c_prev)[1]))

    def test_update_matches_segment_sum(self):
        # one fused iteration == the textbook segment-sum Lloyd update
        x = jnp.asarray(RNG.normal(size=(128, 3)), jnp.float32)
        c1, a0 = ops.kmeans_lloyd(x, 5, iters=1, bp=32, bc=8, interpret=True)
        import jax

        sums = jax.ops.segment_sum(x, a0, num_segments=5)
        cnt = jax.ops.segment_sum(jnp.ones(128), a0, num_segments=5)
        c0, _ = ops.kmeans_lloyd(x, 5, iters=0, bp=32, bc=8, interpret=True)
        want = jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1)[:, None], c0)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_single_pallas_dispatch(self):
        # ONE pallas_call per iteration — and because the iters loop is a
        # lax.scan, the whole multi-iteration pipeline still traces
        # exactly one pallas_call (vs 1 kernel + 2 segment_sums + merge
        # glue per iteration before fusion)
        x = jnp.asarray(RNG.normal(size=(256, 4)), jnp.float32)
        for iters in (1, 5):
            kmeans_lloyd_fused.clear_cache()
            with PallasCallCounter() as spy:
                ops.kmeans_lloyd(x, 8, iters=iters, bp=64, bc=8, fused=True,
                                 interpret=True)
            assert spy.count == 1, iters

    def test_reference_is_multi_dispatch(self):
        # the retained oracle pays an assignment kernel + an update
        # kernel + host merge glue per iteration — the baseline the
        # fusion collapses into one dispatch
        x = jnp.asarray(RNG.normal(size=(256, 4)), jnp.float32)
        kmeans_assign_swizzled.clear_cache()
        kmeans_update_swizzled.clear_cache()
        with PallasCallCounter() as spy:
            ops.kmeans_lloyd(x, 8, iters=1, bp=64, bc=8, fused=False,
                             interpret=True)
        assert spy.count == 2


# ---------------------------------------------------------------------------
# ε-join pair emission
# ---------------------------------------------------------------------------

class TestSimjoinPairs:
    @pytest.mark.parametrize("curve", ["row", "hilbert"])
    @pytest.mark.parametrize("hilbert_order", [False, True])
    def test_pair_set_vs_dense_oracle(self, curve, hilbert_order):
        x = jnp.asarray(RNG.normal(size=(300, 4)) * 0.6, jnp.float32)
        pairs = ops.simjoin_pairs(x, eps=0.7, curve=curve, bp=64,
                                  hilbert_order=hilbert_order, interpret=True)
        got = sorted_pairs(pairs)
        want = ref.simjoin_pairs(x, 0.7)
        assert len(want) > 0
        np.testing.assert_array_equal(got, want)
        assert (got[:, 0] > got[:, 1]).all()  # canonical i > j

    def test_randomized_differential(self):
        for _ in range(5):
            N = int(RNG.integers(2, 300))
            D = int(RNG.integers(1, 6))
            bp = int(RNG.choice([16, 64, 100]))
            eps = float(RNG.uniform(0.2, 1.2))
            ho = bool(RNG.integers(0, 2))
            curve = str(RNG.choice(["row", "hilbert"]))
            x = jnp.asarray(RNG.normal(size=(N, D)) * 0.7, jnp.float32)
            got = sorted_pairs(ops.simjoin_pairs(
                x, eps=eps, curve=curve, bp=bp, hilbert_order=ho,
                interpret=True))
            np.testing.assert_array_equal(
                got, ref.simjoin_pairs(x, eps),
                err_msg=str((N, D, bp, eps, ho, curve)))

    def test_counts_and_pairs_agree(self):
        # both outputs come from the same _hit_tile predicate: the pair
        # multiset must reproduce the per-point neighbour counts
        x = jnp.asarray(RNG.normal(size=(200, 3)) * 0.5, jnp.float32)
        counts = np.asarray(ops.simjoin_counts(x, eps=0.6, bp=64, interpret=True))
        pairs = np.asarray(ops.simjoin_pairs(x, eps=0.6, bp=64, interpret=True))
        from_pairs = np.zeros(200, dtype=np.int64)
        np.add.at(from_pairs, pairs[:, 0], 1)
        np.add.at(from_pairs, pairs[:, 1], 1)
        np.testing.assert_array_equal(from_pairs, counts)

    def test_empty_result(self):
        x = jnp.asarray(np.arange(40, dtype=np.float32).reshape(20, 2) * 100)
        pairs = ops.simjoin_pairs(x, eps=0.1, bp=8, interpret=True)
        assert pairs.shape == (0, 2) and pairs.dtype == jnp.int32

    def test_two_pass_dispatch_count(self):
        x = jnp.asarray(RNG.normal(size=(128, 3)) * 0.5, jnp.float32)
        simjoin_tile_hits_swizzled.clear_cache()
        simjoin_emit_swizzled.clear_cache()
        simjoin_compact.clear_cache()
        with PallasCallCounter() as spy:
            ops.simjoin_pairs(x, eps=0.6, bp=32, interpret=True)
        # count pass + emit pass + the flatten, nothing else
        assert spy.count == 3
