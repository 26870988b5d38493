"""shard_map scale-out of the data-mining apps (PR 5).

Property tests that curve-range partitioning of any schedule is a true
partition (disjoint, covering, contiguous in Hilbert order), and
differential tests that sharded k-means is BIT-identical — and the
distributed two-pass ε-join array-equal — to the single-core fused
kernels on every simulated mesh size, including ragged and degenerate
inputs (N=1, ε=0, K>N).

Mesh sizes above the visible device count skip; CI runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so 1/2/8 all
execute (locally, without the flag, only the 1-device mesh runs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    curve_partition,
    phased_schedule,
    schedule_hilbert_values,
    tile_schedule_nd,
    triangle_schedule,
)
from repro.kernels import ops
from repro.launch.mesh import make_app_mesh

RNG = np.random.default_rng(77)

MESH_SIZES = (1, 2, 8)


@pytest.fixture(scope="module", autouse=True)
def _lean_process_after_module():
    # drop this module's compiled executables (shard_map programs are
    # big) on exit: the ulp-sensitive serve tests flake when the process
    # carries a large live-executable population from earlier files
    yield
    jax.clear_caches()


def app_mesh(num):
    if num > len(jax.devices()):
        pytest.skip(f"needs {num} devices, have {len(jax.devices())} "
                    "(CI sets XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return make_app_mesh(num)


def assert_bit_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ---------------------------------------------------------------------------
# curve_partition is a partition, contiguous in Hilbert order
# ---------------------------------------------------------------------------

class TestCurvePartitionProperties:
    SCHEDULES = [
        ("hilbert-8x8", lambda: tile_schedule_nd("hilbert", (8, 8))),
        ("fur-5x7", lambda: tile_schedule_nd("fur", (5, 7))),
        ("hilbert-3d", lambda: tile_schedule_nd("hilbert", (4, 4, 4))),
        ("triangle-9", lambda: triangle_schedule("hilbert", 9, strict=False)),
        ("phased-fw-4", lambda: phased_schedule("hilbert", 4, kind="fw")),
        ("single-row", lambda: tile_schedule_nd("row", (1, 1))),
    ]

    @pytest.mark.parametrize("name,build", SCHEDULES, ids=[s[0] for s in SCHEDULES])
    @pytest.mark.parametrize("shards", [1, 2, 3, 8, 17])
    def test_partition_properties(self, name, build, shards):
        sched = np.asarray(build())
        bounds = curve_partition(sched, shards)
        # covering + disjoint + contiguous: consecutive half-open ranges
        assert bounds[0] == 0 and bounds[-1] == len(sched)
        sizes = np.diff(bounds)
        assert (sizes >= 0).all() and sizes.sum() == len(sched)
        assert sizes.max() - sizes.min() <= 1  # balanced
        seen = np.concatenate([
            np.arange(bounds[s], bounds[s + 1]) for s in range(shards)
        ])
        np.testing.assert_array_equal(seen, np.arange(len(sched)))

    @pytest.mark.parametrize("shards", [2, 4])
    def test_shards_contiguous_in_hilbert_order(self, shards):
        # each shard of a Hilbert schedule owns a contiguous run of
        # canonical Hilbert values: max of shard s < min of shard s+1
        sched = np.asarray(tile_schedule_nd("hilbert", (8, 8)))
        vals = schedule_hilbert_values(sched)
        bounds = curve_partition(sched, shards)
        prev_max = -1
        for s in range(shards):
            chunk = vals[bounds[s]:bounds[s + 1]]
            assert chunk.min() > prev_max
            np.testing.assert_array_equal(chunk, np.sort(chunk))
            prev_max = chunk.max()

    def test_randomized_partitions(self):
        for _ in range(20):
            n = int(RNG.integers(1, 200))
            s = int(RNG.integers(1, 12))
            bounds = curve_partition(n, s)
            sizes = np.diff(bounds)
            assert bounds[0] == 0 and bounds[-1] == n
            assert sizes.min() >= 0 and sizes.max() - sizes.min() <= 1


# ---------------------------------------------------------------------------
# Sharded k-means: bit-identical to the single-core fused kernel
# ---------------------------------------------------------------------------

class TestShardedKmeans:
    @pytest.mark.parametrize("num", MESH_SIZES)
    @pytest.mark.parametrize("curve", ["fur", "hilbert"])
    def test_bit_identical_across_mesh_sizes(self, num, curve):
        mesh = app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(192, 5)), jnp.float32)
        kw = dict(iters=3, curve=curve, bp=32, bc=8, interpret=True)
        c1, a1 = ops.kmeans_lloyd(x, 12, fused=True, **kw)
        c2, a2 = ops.kmeans_lloyd(x, 12, mesh=mesh, **kw)
        assert_bit_equal(c1, c2, f"centroids num={num} curve={curve}")
        assert_bit_equal(a1, a2, f"assign num={num} curve={curve}")

    @pytest.mark.parametrize("num", MESH_SIZES)
    def test_ragged_and_hilbert_order(self, num):
        mesh = app_mesh(num)
        # N=45 with bp=16: padded point tiles AND padded tile count
        x = jnp.asarray(RNG.normal(size=(45, 3)), jnp.float32)
        kw = dict(iters=3, bp=16, bc=2, hilbert_order=True, interpret=True)
        c1, a1 = ops.kmeans_lloyd(x, 5, **kw)
        c2, a2 = ops.kmeans_lloyd(x, 5, mesh=mesh, **kw)
        assert_bit_equal(c1, c2)
        assert_bit_equal(a1, a2)

    @pytest.mark.parametrize("num", MESH_SIZES)
    def test_degenerate_n1_and_k_gt_n(self, num):
        mesh = app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(1, 4)), jnp.float32)
        for k in (1, 3):  # k=3 > N=1: sampled with replacement
            c1, a1 = ops.kmeans_lloyd(x, k, iters=2, interpret=True)
            c2, a2 = ops.kmeans_lloyd(x, k, iters=2, mesh=mesh,
                                      interpret=True)
            assert_bit_equal(c1, c2, f"k={k}")
            assert_bit_equal(a1, a2, f"k={k}")

    def test_randomized_differential(self):
        num = min(len(jax.devices()), 8)
        mesh = make_app_mesh(num)
        for _ in range(4):
            N = int(RNG.integers(2, 150))
            D = int(RNG.integers(1, 6))
            k = int(RNG.integers(1, min(N, 12) + 1))
            bp = int(RNG.choice([8, 32]))
            bc = int(RNG.choice([4, 8]))
            ho = bool(RNG.integers(0, 2))
            x = jnp.asarray(RNG.normal(size=(N, D)), jnp.float32)
            kw = dict(iters=2, bp=bp, bc=bc, hilbert_order=ho, interpret=True)
            ctx = (num, N, D, k, bp, bc, ho)
            c1, a1 = ops.kmeans_lloyd(x, k, **kw)
            c2, a2 = ops.kmeans_lloyd(x, k, mesh=mesh, **kw)
            assert_bit_equal(c1, c2, str(ctx))
            assert_bit_equal(a1, a2, str(ctx))

    def test_inexact_psum_path_allclose(self):
        num = min(len(jax.devices()), 8)
        mesh = make_app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(128, 4)), jnp.float32)
        kw = dict(iters=3, bp=16, bc=4, interpret=True)
        c1, a1 = ops.kmeans_lloyd(x, 8, **kw)
        c2, a2 = ops.kmeans_lloyd(x, 8, mesh=mesh, shard_exact=False, **kw)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   rtol=1e-5, atol=1e-5)

    def test_collective_structure(self):
        # exact path: 1 psum (counts) + 1 all_gather (per-tile sums);
        # cheap path: 2 psums.  Counted in the traced program — they sit
        # inside the scanned step, i.e. once per Lloyd iteration.
        from repro.kernels.sharded import kmeans_sharded_collectives

        num = min(len(jax.devices()), 8)
        mesh = make_app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(64, 3)), jnp.float32)
        kw = dict(iters=2, bp=16, bc=4, interpret=True)
        assert kmeans_sharded_collectives(x, 4, mesh=mesh, **kw) == {
            "psum": 1, "all_gather": 1}
        assert kmeans_sharded_collectives(x, 4, mesh=mesh, exact=False,
                                          **kw) == {"psum": 2}
        # tree path on a power-of-two mesh: the counts psum plus one
        # butterfly ppermute per doubling round
        if num & (num - 1) == 0 and num > 1:
            assert kmeans_sharded_collectives(
                x, 4, mesh=mesh, reduce="tree", **kw
            ) == {"psum": 1, "ppermute": int(np.log2(num))}

    @pytest.mark.parametrize("num", MESH_SIZES + (3,))
    def test_tree_reduce_bit_stable_and_allclose(self, num):
        # the fixed-topology tree: same bits on repeated runs at every
        # mesh size (incl. the non-power-of-two static pairwise fold),
        # allclose — NOT necessarily bit-equal — to single-core
        mesh = app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(96, 3)), jnp.float32)
        kw = dict(iters=3, bp=16, bc=4, shard_reduce="tree", interpret=True)
        c1, a1 = ops.kmeans_lloyd(x, 6, mesh=mesh, **kw)
        c2, a2 = ops.kmeans_lloyd(x, 6, mesh=mesh, **kw)
        assert_bit_equal(c1, c2, f"tree reduce unstable num={num}")
        assert_bit_equal(a1, a2)
        cs, _ = ops.kmeans_lloyd(x, 6, iters=3, bp=16, bc=4, interpret=True)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(cs),
                                   rtol=1e-5, atol=1e-5)

    def test_reduce_validates(self):
        from repro.kernels.sharded import kmeans_lloyd_sharded

        x = jnp.asarray(RNG.normal(size=(32, 3)), jnp.float32)
        with pytest.raises(ValueError, match="reduce"):
            kmeans_lloyd_sharded(x, 4, mesh=make_app_mesh(1),
                                 reduce="ring", interpret=True)


# ---------------------------------------------------------------------------
# Sharded ε-join: same pairs, same order, on every mesh size
# ---------------------------------------------------------------------------

class TestShardedSimjoin:
    @pytest.mark.parametrize("num", MESH_SIZES)
    @pytest.mark.parametrize("hilbert_order", [False, True])
    def test_pairs_equal_single_core(self, num, hilbert_order):
        mesh = app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(200, 4)) * 0.6, jnp.float32)
        kw = dict(eps=0.8, bp=32, hilbert_order=hilbert_order,
                  interpret=True)
        p1 = np.asarray(ops.simjoin_pairs(x, **kw))
        p2 = np.asarray(ops.simjoin_pairs(x, mesh=mesh, **kw))
        # contiguous schedule ranges preserve the single-core emission
        # order, so the result is array-equal (stronger than set-equal)
        np.testing.assert_array_equal(p1, p2)
        assert (p2[:, 0] > p2[:, 1]).all()

    @pytest.mark.parametrize("num", MESH_SIZES)
    def test_degenerate_inputs(self, num):
        mesh = app_mesh(num)
        # N=1: no pairs
        x1 = jnp.asarray(RNG.normal(size=(1, 3)), jnp.float32)
        assert ops.simjoin_pairs(x1, eps=5.0, mesh=mesh,
                                 interpret=True).shape == (0, 2)
        # N=0: no pairs
        x0 = jnp.zeros((0, 3), jnp.float32)
        assert ops.simjoin_pairs(x0, eps=1.0, mesh=mesh,
                                 interpret=True).shape == (0, 2)
        # ε=0: exactly the duplicate pairs
        xd = jnp.asarray(
            np.array([[1, 2], [3, 4], [1, 2], [5, 6], [3, 4], [1, 2]],
                     np.float32))
        p1 = np.asarray(ops.simjoin_pairs(xd, eps=0.0, bp=4, interpret=True))
        p2 = np.asarray(ops.simjoin_pairs(xd, eps=0.0, bp=4, mesh=mesh,
                                          interpret=True))
        np.testing.assert_array_equal(p1, p2)
        # empty result (eps too small for spread-out points)
        xs = jnp.asarray(np.arange(40, dtype=np.float32).reshape(20, 2) * 100)
        assert ops.simjoin_pairs(xs, eps=0.1, bp=8, mesh=mesh,
                                 interpret=True).shape == (0, 2)

    def test_randomized_differential(self):
        num = min(len(jax.devices()), 8)
        mesh = make_app_mesh(num)
        for _ in range(4):
            N = int(RNG.integers(2, 250))
            D = int(RNG.integers(1, 5))
            bp = int(RNG.choice([16, 64]))
            eps = float(RNG.uniform(0.2, 1.0))
            ho = bool(RNG.integers(0, 2))
            x = jnp.asarray(RNG.normal(size=(N, D)) * 0.7, jnp.float32)
            ctx = (num, N, D, bp, eps, ho)
            p1 = np.asarray(ops.simjoin_pairs(
                x, eps=eps, bp=bp, hilbert_order=ho, interpret=True))
            p2 = np.asarray(ops.simjoin_pairs(
                x, eps=eps, bp=bp, hilbert_order=ho, mesh=mesh,
                interpret=True))
            np.testing.assert_array_equal(p1, p2, err_msg=str(ctx))

    def test_counts_consistent_with_sharded_pairs(self):
        num = min(len(jax.devices()), 8)
        mesh = make_app_mesh(num)
        x = jnp.asarray(RNG.normal(size=(150, 3)) * 0.5, jnp.float32)
        counts = np.asarray(ops.simjoin_counts(x, eps=0.6, bp=32,
                                               interpret=True))
        pairs = np.asarray(ops.simjoin_pairs(x, eps=0.6, bp=32, mesh=mesh,
                                             interpret=True))
        from_pairs = np.zeros(150, dtype=np.int64)
        np.add.at(from_pairs, pairs[:, 0], 1)
        np.add.at(from_pairs, pairs[:, 1], 1)
        np.testing.assert_array_equal(from_pairs, counts)


def test_mesh_rejects_fused_false():
    # mesh= always runs the sharded fused path: an explicit fused=False
    # must fail loudly, not be silently ignored
    x = jnp.asarray(RNG.normal(size=(32, 3)), jnp.float32)
    with pytest.raises(ValueError, match="fused=False"):
        ops.kmeans_lloyd(x, 4, mesh=make_app_mesh(1), fused=False,
                         interpret=True)


def test_sharded_join_budget_fallback_set_equal():
    # the sharded emission keeps no data-sized VMEM buffer either: a
    # tiny budget changes nothing, and the pair set equals the oracle's
    from repro.core import set_vmem_budget
    from repro.kernels import ref

    x = jnp.asarray(RNG.normal(size=(60, 3)) * 0.6, jnp.float32)
    old = set_vmem_budget(64)
    try:
        got = np.asarray(ops.simjoin_pairs(x, eps=0.8, bp=16,
                                           mesh=make_app_mesh(1),
                                           interpret=True))
    finally:
        set_vmem_budget(old)
    got = got[np.lexsort((got[:, 1], got[:, 0]))]
    np.testing.assert_array_equal(got, ref.simjoin_pairs(x, 0.8))


def test_mesh_helper_validates():
    with pytest.raises(ValueError):
        make_app_mesh(0)
    with pytest.raises(ValueError):
        make_app_mesh(-3)
    with pytest.raises(ValueError):
        make_app_mesh(len(jax.devices()) + 1)
    from repro.kernels.sharded import mesh_axis

    mesh = make_app_mesh(1)
    axis, num = mesh_axis(mesh)
    assert axis == "shards" and num == 1


def test_mesh_axis_rejects_multiaxis():
    from jax.sharding import Mesh

    from repro.kernels.sharded import mesh_axis

    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    with pytest.raises(ValueError, match="1-D mesh"):
        mesh_axis(Mesh(dev, ("a", "b")))


def test_curve_partition_more_shards_than_steps():
    # num > steps: trailing shards own empty (but valid) ranges — the
    # SPMD apps pad those shards with inert rows
    bounds = curve_partition(3, 8)
    sizes = np.diff(bounds)
    assert bounds[0] == 0 and bounds[-1] == 3
    assert (sizes >= 0).all() and sizes.sum() == 3
    assert (sizes[3:] == 0).all()
    with pytest.raises(ValueError):
        curve_partition(3, 0)


# ---------------------------------------------------------------------------
# Halo exchange: the point-sharded join vs single-core
# ---------------------------------------------------------------------------

class TestHaloJoin:
    @pytest.mark.parametrize("num", MESH_SIZES)
    @pytest.mark.parametrize("hilbert_order", [False, True])
    @pytest.mark.parametrize("curve", ["hilbert", "zorder"])
    def test_halo_equals_single_core(self, num, hilbert_order, curve):
        # each curve's schedule order, through the mesh, is one core's
        from repro.kernels.sharded import simjoin_pairs_sharded

        mesh = app_mesh(num)
        x = jnp.asarray(RNG.uniform(size=(300, 2)), jnp.float32)
        kw = dict(bp=32, curve=curve, hilbert_order=hilbert_order,
                  interpret=True)
        p0 = np.asarray(ops.simjoin_pairs(x, eps=0.07, **kw))
        ph = np.asarray(simjoin_pairs_sharded(x, 0.07, mesh=mesh, **kw))
        assert len(p0) > 0
        np.testing.assert_array_equal(p0, ph)

    @pytest.mark.parametrize("num", MESH_SIZES)
    def test_halo_edge_cases(self, num):
        from repro.kernels.sharded import simjoin_pairs_sharded

        mesh = app_mesh(num)
        # N=1 / empty result / ε=0 duplicates, all through the halo path
        x1 = jnp.asarray(RNG.normal(size=(1, 3)), jnp.float32)
        assert simjoin_pairs_sharded(x1, 5.0, mesh=mesh,
                                     interpret=True).shape == (0, 2)
        xd = jnp.asarray(np.array(
            [[1, 2], [3, 4], [1, 2], [5, 6], [3, 4], [1, 2]], np.float32))
        p1 = np.asarray(ops.simjoin_pairs(xd, eps=0.0, bp=4, interpret=True))
        p2 = np.asarray(simjoin_pairs_sharded(xd, 0.0, mesh=mesh, bp=4,
                                              interpret=True))
        np.testing.assert_array_equal(p1, p2)
        xs = jnp.asarray(np.arange(40, dtype=np.float32).reshape(20, 2) * 100)
        assert simjoin_pairs_sharded(xs, 0.1, mesh=mesh, bp=8,
                                     interpret=True).shape == (0, 2)

    def test_halo_volume_below_replicated_and_sublinear(self):
        # halo bytes/shard strictly under full replication (the point
        # buffer to every shard, once per pass), and sublinear in N at
        # fixed point density (4× the points in 4× the area → ~2× the
        # boundary, 4× the replication)
        from repro.kernels.sharded import simjoin_sharded_volume

        num = min(len(jax.devices()), 8)
        if num < 2:
            pytest.skip("needs a real mesh for cross-shard traffic")
        mesh = make_app_mesh(num)
        rng = np.random.default_rng(5)
        vols = {}
        for N, side in [(512, 1.0), (2048, 2.0)]:
            x = jnp.asarray((rng.uniform(size=(N, 2)) * side), jnp.float32)
            kw = dict(mesh=mesh, bp=64, hilbert_order=True, interpret=True)
            vh = simjoin_sharded_volume(x, 0.05, **kw)
            replicated = 2 * x.nbytes  # N % bp == 0: x is the point buffer
            assert vh["counts"].get("ppermute", 0) > 0
            assert 0 < vh["bytes_per_shard"] < replicated
            vols[N] = vh["bytes_per_shard"]
        halo_ratio = vols[2048] / vols[512]
        assert halo_ratio < 3.0  # boundary-area scaling, not volume


# ---------------------------------------------------------------------------
# int32 offset overflow: raised, not assert (guards survive python -O)
# ---------------------------------------------------------------------------

class TestPairOffsetOverflow:
    def test_single_core_raises(self, monkeypatch):
        from repro.kernels import ops as ops_mod
        from repro.kernels import simjoin as simjoin_mod

        def fake_totals(sched, start, live, xp, **kw):
            return jnp.full((kw["steps"],), 2**25 * kw["bp"], jnp.int32)

        # ops.simjoin_pairs now delegates to the shared scheduled driver,
        # so the count pass is intercepted at its home module
        monkeypatch.setattr(simjoin_mod, "simjoin_totals", fake_totals)
        x = jnp.asarray(RNG.normal(size=(64, 3)), jnp.float32)
        with pytest.raises(ValueError, match="overflow"):
            ops_mod.simjoin_pairs(x, eps=0.5, bp=32, interpret=True)

    def test_sharded_raises(self, monkeypatch):
        from repro.kernels import sharded

        mesh = make_app_mesh(1)
        x = jnp.asarray(RNG.normal(size=(64, 3)) * 0.1, jnp.float32)

        def fake_pass1(mesh, axis, **kw):
            def fn(sched, xs, *tables):
                hits = jnp.full((sched.shape[0], kw["bp"]), 2**25,
                                jnp.int32)
                return hits, xs
            return fn
        monkeypatch.setattr(sharded, "_halo_pass1_fn", fake_pass1)
        with pytest.raises(ValueError, match="overflow"):
            sharded.simjoin_pairs_sharded(x, 0.5, mesh=mesh, bp=32,
                                          interpret=True)
