"""CurveProgram execution layer (PR 5): launch() dispatch parity, the
VMEM residency estimate and its budget-gated fallback to the retained
reference paths, and the schedule-cache registry that keeps
schedule_cache_clear() exhaustive.

All kernels run in interpret mode (CPU container; TPU is the target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CurveProgram,
    curve_partition,
    fits_vmem,
    get_vmem_budget,
    register_schedule_cache,
    schedule_cache_clear,
    set_vmem_budget,
    tile_schedule_device,
)
from repro.kernels import ops, ref
from repro.kernels.cholesky import cholesky_blocked, cholesky_program
from repro.kernels.floyd_warshall import floyd_warshall_blocked, fw_program
from repro.kernels.kmeans import _cached_order
from repro.kernels.launch import count_collectives, launch
from repro.kernels.pallas_compat import PallasCallCounter

RNG = np.random.default_rng(55)


@pytest.fixture(scope="module", autouse=True)
def _lean_process_after_module():
    # drop this module's compiled executables on exit: the ulp-sensitive
    # serve tests (test_substrates) flake when the process carries a
    # large live-executable population from earlier files
    yield
    jax.clear_caches()


@pytest.fixture
def no_budget():
    """Run with no VMEM budget, restoring whatever was set before."""
    old = set_vmem_budget(None)
    yield
    set_vmem_budget(old)


def rand_digraph(n, p=0.25):
    w = RNG.uniform(1, 10, size=(n, n)).astype(np.float32)
    d = np.where(RNG.uniform(size=(n, n)) < p, w, np.inf).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    return jnp.asarray(d)


def rand_spd(n):
    m = RNG.normal(size=(n, n)).astype(np.float32)
    return jnp.asarray(m @ m.T + n * np.eye(n, dtype=np.float32))


# ---------------------------------------------------------------------------
# launch(): one dispatch, same bits as a hand-rolled pallas_call
# ---------------------------------------------------------------------------

class TestLaunch:
    def test_minimal_program_roundtrip(self):
        # a 2x-scaling copy program driven by a permuted schedule
        from jax.experimental import pallas as pl

        sched = jnp.asarray([[2], [0], [1], [3]], dtype=jnp.int32)

        def kernel(sched_ref, x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
        program = CurveProgram(
            name="double",
            schedule=sched,
            kernel=kernel,
            in_specs=(pl.BlockSpec((1, 8), lambda s, sr: (sr[s, 0], 0)),),
            out_specs=pl.BlockSpec((1, 8), lambda s, sr: (sr[s, 0], 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        )
        with PallasCallCounter() as spy:
            out = launch(program, x, interpret=True)
        assert spy.count == 1
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2)

    def test_all_fused_apps_single_dispatch_through_launch(self, no_budget):
        # the acceptance invariant: every fused app is exactly one
        # pallas_call, now issued by launch() instead of bespoke wrappers
        d = rand_digraph(32)
        a = rand_spd(32)
        x = jnp.asarray(RNG.normal(size=(128, 4)), jnp.float32)
        from repro.kernels.kmeans import kmeans_lloyd_fused

        cases = [
            (floyd_warshall_blocked,
             lambda: ops.floyd_warshall(d, b=8, interpret=True)),
            (cholesky_blocked,
             lambda: ops.cholesky(a, b=8, interpret=True)),
            (kmeans_lloyd_fused,
             lambda: ops.kmeans_lloyd(x, 8, iters=2, bp=32, bc=4,
                                      interpret=True)),
        ]
        for jitted, call in cases:
            jitted.clear_cache()
            with PallasCallCounter() as spy:
                jax.block_until_ready(jax.tree_util.tree_leaves(call()))
            assert spy.count == 1, jitted

    def test_matmul_through_launch(self):
        a = jnp.asarray(RNG.normal(size=(64, 32)), jnp.float32)
        b = jnp.asarray(RNG.normal(size=(32, 48)), jnp.float32)
        for nd in (2, 3):
            out = ops.matmul(a, b, bm=16, bn=16, bk=16, schedule_ndim=nd,
                             curve="hilbert", interpret=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(a) @ np.asarray(b),
                rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# vmem_bytes + budget gate
# ---------------------------------------------------------------------------

class TestVmemBudget:
    def test_fw_estimate_matches_hand_count(self):
        # the matrix stays in HBM (pl.ANY): only scratch counts — the
        # DMA'd tile, the closed diagonal and both panels, f32
        nt, b = 4, 16
        n = nt * b
        prog = fw_program("hilbert", nt, b)
        d = jax.ShapeDtypeStruct((n, n), jnp.float32)
        want = 4 * (b * b + b * b + 2 * b * n)
        assert prog.vmem_bytes(d) == want

    def test_cholesky_estimate(self):
        nt, b = 4, 16
        n = nt * b
        prog = cholesky_program("hilbert", nt, b)
        a = jax.ShapeDtypeStruct((n, n), jnp.float32)
        assert prog.vmem_bytes(a) == 4 * (b * b + b * b + b * n)

    def test_operand_count_checked(self):
        prog = fw_program("hilbert", 2, 8)
        with pytest.raises(ValueError):
            prog.vmem_bytes()

    def test_budget_accessors(self):
        old = set_vmem_budget(12345)
        try:
            assert get_vmem_budget() == 12345
            assert set_vmem_budget(None) == 12345
            # None = explicitly unlimited
            assert get_vmem_budget() is None
        finally:
            set_vmem_budget(old)

    def test_fits_vmem_unlimited_by_default(self, no_budget):
        prog = fw_program("hilbert", 2, 8)
        d = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        assert fits_vmem(prog, d)

    @pytest.mark.parametrize("app", ["fw", "chol", "kmeans"])
    def test_fallback_is_multi_dispatch_and_equal(self, app, no_budget):
        # with a 1 KiB budget every fused form is rejected; the wrapper
        # must take the retained reference path (multi-dispatch) and the
        # result must equal the fused one exactly
        from repro.kernels.cholesky import cholesky_blocked_reference
        from repro.kernels.floyd_warshall import (
            floyd_warshall_blocked_reference,
        )
        from repro.kernels.kmeans import (
            kmeans_assign_swizzled,
            kmeans_lloyd_fused,
            kmeans_update_swizzled,
        )
        from repro.kernels.matmul import tile_update_swizzled

        if app == "fw":
            arg = rand_digraph(48)
            call = lambda: ops.floyd_warshall(arg, b=16, interpret=True)
            caches = [floyd_warshall_blocked, floyd_warshall_blocked_reference]
        elif app == "chol":
            arg = rand_spd(48)
            call = lambda: ops.cholesky(arg, b=16, interpret=True)
            caches = [cholesky_blocked, cholesky_blocked_reference,
                      tile_update_swizzled]
        else:
            arg = jnp.asarray(RNG.normal(size=(96, 3)), jnp.float32)
            call = lambda: ops.kmeans_lloyd(arg, 6, iters=2, bp=32, bc=2,
                                            interpret=True)
            caches = [kmeans_lloyd_fused, kmeans_assign_swizzled,
                      kmeans_update_swizzled]
        fused_out = call()
        old = set_vmem_budget(1024)
        try:
            for c in caches:
                c.clear_cache()
            with PallasCallCounter() as spy:
                # the gate routes loudly, never silently
                with pytest.warns(RuntimeWarning, match="VMEM"):
                    ref_out = call()
            assert spy.count > 1  # reference path = multi-dispatch
        finally:
            set_vmem_budget(old)
        for f, r in zip(jax.tree_util.tree_leaves(fused_out),
                        jax.tree_util.tree_leaves(ref_out)):
            np.testing.assert_array_equal(np.asarray(f), np.asarray(r))

    def test_simjoin_fallback_to_dense_oracle(self, no_budget):
        # the join keeps no data-sized buffer in VMEM, so even a 64-byte
        # budget leaves it on its kernel path (two passes and the
        # flatten) — and its pairs still equal the dense oracle's
        from repro.kernels.simjoin import (
            simjoin_compact,
            simjoin_emit_swizzled,
            simjoin_tile_hits_swizzled,
        )

        x = jnp.asarray(RNG.normal(size=(50, 3)) * 0.6, jnp.float32)
        want = ref.simjoin_pairs(x, 0.8)
        old = set_vmem_budget(64)
        try:
            simjoin_tile_hits_swizzled.clear_cache()
            simjoin_emit_swizzled.clear_cache()
            simjoin_compact.clear_cache()
            with PallasCallCounter() as spy:
                got = np.asarray(ops.simjoin_pairs(x, eps=0.8, bp=16,
                                                   interpret=True))
            assert spy.count == 3
        finally:
            set_vmem_budget(old)
        got = got[np.lexsort((got[:, 1], got[:, 0]))]
        np.testing.assert_array_equal(got, want)

    def test_env_var_budget(self, no_budget, monkeypatch):
        from repro.core import VMEM_BUDGET_DEFAULT

        monkeypatch.setenv("REPRO_VMEM_BUDGET", "1024")
        # an explicit None (the no_budget fixture) overrides the env var…
        assert get_vmem_budget() is None
        # …and restoring the default defers to it
        set_vmem_budget(VMEM_BUDGET_DEFAULT)
        assert get_vmem_budget() == 1024
        prog = fw_program("hilbert", 2, 8)
        d = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        assert not fits_vmem(prog, d)


# ---------------------------------------------------------------------------
# schedule-cache registry (the PR-5 bugfix)
# ---------------------------------------------------------------------------

class TestCacheRegistry:
    def test_point_order_cache_is_cleared(self):
        # the PR-4 gap: hilbert_point_order_cached was missed by
        # schedule_cache_clear and leaked across curve re-registrations
        x = jnp.asarray(RNG.normal(size=(64, 3)), jnp.float32)
        from repro.kernels.kmeans import hilbert_point_order_cached

        hilbert_point_order_cached(x)
        assert _cached_order.cache_info().currsize > 0
        schedule_cache_clear()
        assert _cached_order.cache_info().currsize == 0

    def test_schedule_caches_cleared(self):
        tile_schedule_device("hilbert", (4, 4))
        from repro.core.schedule import _device_schedule

        assert _device_schedule.cache_info().currsize > 0
        schedule_cache_clear()
        assert _device_schedule.cache_info().currsize == 0

    def test_sharded_builders_registered(self):
        # the shard_map program builders capture curve-derived tables,
        # so they must be in the registry too
        from repro.core.schedule import _REGISTERED_CACHES
        from repro.kernels import sharded

        assert sharded._lloyd_fn in _REGISTERED_CACHES
        assert sharded._join_pass1_fn in _REGISTERED_CACHES
        assert sharded._join_pass2_fn in _REGISTERED_CACHES

    def test_register_rejects_non_caches(self):
        with pytest.raises(TypeError):
            register_schedule_cache(object())


# ---------------------------------------------------------------------------
# curve_partition (unit tests; the property sweep lives in
# tests/test_apps_sharded.py next to its consumers)
# ---------------------------------------------------------------------------

class TestCurvePartition:
    def test_balanced_bounds(self):
        bounds = curve_partition(10, 4)
        np.testing.assert_array_equal(bounds, [0, 3, 6, 8, 10])

    def test_more_shards_than_rows(self):
        bounds = curve_partition(2, 5)
        assert bounds[0] == 0 and bounds[-1] == 2
        sizes = np.diff(bounds)
        assert sizes.max() <= 1 and sizes.sum() == 2

    def test_accepts_schedule_array(self):
        sched = np.zeros((7, 2), np.int32)
        bounds = curve_partition(sched, 3)
        assert bounds[-1] == 7 and len(bounds) == 4

    def test_invalid_shards(self):
        with pytest.raises(ValueError):
            curve_partition(4, 0)


def test_count_collectives_sees_through_scan_and_jit():
    def f(x):
        def step(c, _):
            return c + x, None
        c, _ = jax.lax.scan(step, x, None, length=3)
        return c

    assert count_collectives(jax.jit(f), jnp.ones(3)) == {}
