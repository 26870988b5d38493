"""Streaming-service exactness: tick-coalesced outputs == batch oracles.

The coalescing contract of serve/apps.py, asserted:

* ε-join — ANY interleaving of insert/query commands across ticks
  accumulates a pair set EQUAL to the one-shot batch join
  (``ops.simjoin_pairs``) on the union of inserted points (randomised
  command scripts, both coalesce modes; hypothesis widens the script
  space when the [test] extra is installed);
* Lloyd — streaming with decay=1.0 over a fully-inserted set is
  BIT-identical to ``ops.kmeans_lloyd`` after the same number of
  iterations (including ragged-N and padded-K shapes);
* the resident index's sorted merge equals a stable re-sort of the
  union, and its halo LRU participates in ``schedule_cache_clear``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.serve.apps import StreamKMeans, StreamSimJoin

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # optional [test] extra; CI installs it
    HAVE_HYPOTHESIS = False

EPS = 0.12


def _points(seed, n, d=2):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, size=(n, d)).astype(np.float32)


def _random_script(rng, max_cmds=12):
    """A command script: insert m points, query m points, or end a tick."""
    script = []
    for _ in range(rng.integers(1, max_cmds + 1)):
        roll = rng.random()
        if roll < 0.5:
            script.append(("insert", int(rng.integers(1, 17))))
        elif roll < 0.75:
            script.append(("query", int(rng.integers(1, 7))))
        else:
            script.append(("tick", 0))
    return script


def _check_interleaving(script, seed, fifo):
    """Drive one command script; compare against the batch oracle."""
    rng = np.random.default_rng(seed)
    svc = StreamSimJoin(
        EPS, bp=16, bounds=(np.zeros(2), np.ones(2)),
        coalesce="fifo" if fifo else "hilbert", interpret=True,
    )
    for cmd, m in script:
        if cmd == "insert":
            svc.insert(rng.uniform(0, 1, size=(m, 2)).astype(np.float32))
        elif cmd == "query":
            svc.query(rng.uniform(0, 1, size=(m, 2)).astype(np.float32))
        else:
            svc.tick()
    svc.run_until_idle()
    union = svc.points_by_id()
    got = svc.pairs()
    if len(union) == 0:
        assert len(got) == 0
        return
    want = np.asarray(
        ops.simjoin_pairs(jnp.asarray(union), EPS, interpret=True),
        dtype=np.int64,
    )
    want = want[np.lexsort((want[:, 1], want[:, 0]))]
    np.testing.assert_array_equal(got, want)
    # the index stayed sorted-merged, never re-sorted: equal to the
    # stable lexsort of the union by (key, id)
    keys = svc._point_keys(union)
    ids = np.arange(len(union), dtype=np.int64)
    order = np.lexsort((ids, keys))
    np.testing.assert_array_equal(svc._ids, ids[order])
    np.testing.assert_array_equal(svc._keys, keys[order])
    np.testing.assert_array_equal(svc._pts, union[order])


class TestStreamingJoinExactness:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_interleaving_matches_batch_join(self, seed):
        rng = np.random.default_rng(1000 + seed)
        _check_interleaving(_random_script(rng), seed, fifo=seed % 2 == 1)

    def test_query_results_match_brute_force(self):
        svc = StreamSimJoin(
            EPS, bp=16, bounds=(np.zeros(2), np.ones(2)), interpret=True
        )
        pts = _points(3, 60)
        svc.insert(pts)
        probes = _points(4, 7)
        t = svc.query(probes)
        svc.tick()  # inserts admitted first, then queries probe them
        d2 = np.sum((probes[:, None] - pts[None]) ** 2, axis=-1)
        want = sorted(
            (i, j) for i, j in zip(*np.nonzero(d2 <= EPS * EPS))
        )
        got = sorted((int(a), int(b)) for a, b in t.result)
        assert got == want

    def test_queries_do_not_join_the_set(self):
        svc = StreamSimJoin(
            EPS, bp=16, bounds=(np.zeros(2), np.ones(2)), interpret=True
        )
        svc.insert(_points(5, 20))
        svc.query(_points(6, 10))
        svc.tick()
        assert svc.resident_count == 20
        assert len(svc.points_by_id()) == 20

    def test_halo_cache_registered_with_schedule_registry(self):
        from repro.core.schedule import schedule_cache_clear
        from repro.serve.apps import _halo_cache

        svc = StreamSimJoin(
            EPS, bp=16, bounds=(np.zeros(2), np.ones(2)), interpret=True
        )
        svc.insert(_points(7, 40))
        svc.tick()
        svc.insert(_points(8, 10))
        svc.tick()
        assert _halo_cache.cache_info().currsize > 0
        schedule_cache_clear()
        assert _halo_cache.cache_info().currsize == 0

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="eps"):
            StreamSimJoin(0.0)
        with pytest.raises(ValueError, match="coalesce"):
            StreamSimJoin(0.1, coalesce="lifo")


if HAVE_HYPOTHESIS:
    _script = st.lists(
        st.one_of(
            st.tuples(st.just("insert"), st.integers(1, 16)),
            st.tuples(st.just("query"), st.integers(1, 6)),
            st.tuples(st.just("tick"), st.just(0)),
        ),
        min_size=1,
        max_size=12,
    )

    class TestStreamingJoinProperty:
        @settings(max_examples=10, deadline=None)
        @given(script=_script, seed=st.integers(0, 2**16), fifo=st.booleans())
        def test_any_interleaving_matches_batch_join(self, script, seed, fifo):
            _check_interleaving(script, seed, fifo)


class TestStreamingLloydExactness:
    @pytest.mark.parametrize(
        "N,k,bp,bc",
        [
            (200, 5, 64, 8),    # ragged N (200 % 64 != 0), padded K
            (256, 8, 64, 8),    # exact tiling
            (90, 4, 128, 16),   # bp, bc clamp to N, k
        ],
    )
    def test_decay_one_bit_identical_to_batch(self, N, k, bp, bc):
        pts = _points(11, N, d=3)
        svc = StreamKMeans(k, bp=bp, bc=bc, interpret=True)
        for chunk in np.array_split(pts, 4):
            svc.insert(chunk)
        T = 4
        for _ in range(T):
            svc.tick()
        c_b, a_b = ops.kmeans_lloyd(
            jnp.asarray(svc.points()), k, iters=T, bp=bp, bc=bc,
            interpret=True,
        )
        np.testing.assert_array_equal(svc.centroids(), np.asarray(c_b))
        np.testing.assert_array_equal(svc.assignment(), np.asarray(a_b))

    def test_decayed_state_tracks_drift(self):
        """decay<1: old mass fades — after the stream jumps to a new
        region, centroids follow it (a smoke property, not bit-exact)."""
        svc = StreamKMeans(2, decay=0.5, bp=64, bc=8, interpret=True)
        svc.insert(_points(12, 80) * 0.1)  # cluster near origin
        for _ in range(3):
            svc.tick()
        for _ in range(6):
            svc.insert(_points(13, 40) * 0.1 + 0.9)  # jump to (0.9, 1.0)
            svc.tick()
        c = svc.centroids()
        assert c is not None and np.isfinite(c).all()
        assert c.max() > 0.5  # mass followed the drift

    def test_assign_command_matches_reference(self):
        svc = StreamKMeans(4, bp=64, bc=8, interpret=True)
        svc.insert(_points(14, 120))
        svc.tick()
        # a tick admits its commands BEFORE its Lloyd step, so assign
        # answers against the centroids as they were at admission
        at_admission = svc.centroids()
        probes = _points(15, 17)
        t1 = svc.assign(probes[:9])
        t2 = svc.assign(probes[9:])
        svc.tick()
        _, want = ref.kmeans_assign(
            jnp.asarray(probes), jnp.asarray(at_admission)
        )
        got = np.concatenate([t1.result, t2.result])
        np.testing.assert_array_equal(got, np.asarray(want))

    def test_assign_before_init_returns_none(self):
        svc = StreamKMeans(4, interpret=True)
        t = svc.assign(_points(16, 3))
        svc.tick()
        assert t.done and t.result is None

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="k must"):
            StreamKMeans(0)
        with pytest.raises(ValueError, match="decay"):
            StreamKMeans(3, decay=0.0)
        with pytest.raises(ValueError, match="decay"):
            StreamKMeans(3, decay=1.5)
        with pytest.raises(ValueError, match="coalesce"):
            StreamKMeans(3, coalesce="lifo")


class TestProgramTickMetadata:
    def test_signature_and_with_schedule(self):
        from repro.core.schedule import kmeans_schedule_device
        from repro.kernels.kmeans import kmeans_lloyd_program

        sched = kmeans_schedule_device("fur", 2, 1)
        prog = kmeans_lloyd_program(
            sched, pt=2, ct=1, bp=4, bc=4, D=2, k_valid=None, n_valid=None,
            choice="fur",
        )
        name, steps, grid, cols, choice_key = prog.signature
        assert name == "kmeans_lloyd_fused" and steps == prog.steps
        assert grid == (prog.steps,) and cols == prog.columns
        assert choice_key == "kmeans|fur|4x4"
        # same-arity schedule swaps in; the rest of the declaration rides
        sched2 = kmeans_schedule_device("hilbert", 2, 1)
        prog2 = prog.with_schedule(sched2)
        assert prog2.kernel is prog.kernel and prog2.name == prog.name
        assert prog2.signature == prog.signature
        # with choice= the swap updates the recorded choice (and signature)
        prog3 = prog.with_schedule(
            sched2, choice=prog.choice.with_(curve="hilbert")
        )
        assert prog3.signature[-1] == "kmeans|hilbert|4x4"
        assert prog3.signature != prog.signature
        # wrong column arity is rejected
        with pytest.raises(ValueError, match="columns"):
            prog.with_schedule(np.zeros((5, 2), dtype=np.int32))
