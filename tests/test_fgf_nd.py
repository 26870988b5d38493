"""d-dimensional FGF jump-over + schedule-model tests (PR 2).

Covers the output-linear generation refactor's contract:
  * subcube-state algebra (`decode_from_state_nd`, `child_state_nd`)
    bit-identical to the top-down codec and, at d = 2, to the paper's
    Mealy tables (the U/D/A/C patterns ARE the 4 reachable signed perms);
  * jump-over output == `clip_path_nd` (rows AND canonical Hilbert
    values) on randomized shapes for d ∈ {2, 3, 4};
  * triangle/band/intersect/predicate regions vs. filter oracles, and
    2-D bit-identity with the table-driven `fgf` walker;
  * counting classifier: decode work ∝ output size, not cover volume;
  * vectorised `min_revisit_gap` and one-pass `miss_counts` /
    `reuse_distances` vs. their reference simulators (randomized);
  * `triangle_schedule_nd` in any dimension;
  * `benchmarks.run --json` fails on zero collected rows.
"""
import numpy as np
import pytest

from repro.core import fgf, fgf_nd
from repro.core import hilbert_nd as hn
from repro.core import schedule as sched_mod
from repro.core.hilbert import (
    _DEC_IJ,
    _DEC_NEXT,
    canonical_start_state,
    decode_from_state,
)
from repro.core.hilbert_nd import (
    apply_state_nd,
    canonical_start_state_nd,
    child_corner_nd,
    child_state_nd,
    child_transforms_nd,
    decode_from_state_nd,
    hilbert_decode_raw_nd,
    identity_state_nd,
)
from repro.core.schedule import (
    lru_misses,
    min_revisit_gap,
    miss_counts,
    miss_curve,
    pair_stream,
    reuse_distances,
    tile_schedule_nd,
    triangle_schedule,
    triangle_schedule_nd,
)

RNG = np.random.default_rng(11)


def random_shapes(d: int, n: int, hi: int) -> list[tuple[int, ...]]:
    return [
        tuple(int(RNG.integers(1, hi)) for _ in range(d)) for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# Subcube-state algebra (the tentpole's refactor layer)
# ---------------------------------------------------------------------------

class TestSubcubeStates:
    @pytest.mark.parametrize("d", [2, 3, 4])
    def test_children_tile_the_parent(self, d):
        # descending one level with (child_state, child_corner) reproduces
        # the parent's decode exactly — for a non-identity parent too
        for parent in [identity_state_nd(d), child_transforms_nd(d)[3][1]]:
            levels = 2
            want = decode_from_state_nd(
                np.arange(1 << (d * levels)), levels, parent, d
            )
            sub = 1 << (d * (levels - 1))
            for w in range(1 << d):
                got = np.asarray(
                    child_corner_nd(parent, w, d), dtype=np.int64
                ) * (1 << (levels - 1)) + decode_from_state_nd(
                    np.arange(sub), levels - 1, child_state_nd(parent, w, d), d
                )
                np.testing.assert_array_equal(want[w * sub:(w + 1) * sub], got)

    @pytest.mark.parametrize("d", [2, 3, 4])
    def test_canonical_start_matches_codec(self, d):
        # canonical decode of a depth-L grid == reference decode re-oriented
        # by the canonical start state (the period-d orientation cycling)
        for levels in (1, 2, 3):
            h = np.arange(1 << (d * levels))
            np.testing.assert_array_equal(
                hn.hilbert_decode_nd(h, d, levels),
                decode_from_state_nd(
                    h, levels, canonical_start_state_nd(levels, d), d
                ),
            )

    def test_states_are_the_mealy_patterns_2d(self):
        # each Mealy state (U, D, A, C) is realised by exactly one signed
        # permutation, and the transition/corner tables coincide
        h = np.arange(16)
        state_of = {}
        signed_perms = [
            ((p0, p1), f) for p0, p1 in ((0, 1), (1, 0)) for f in range(4)
        ]
        for mealy in range(4):
            i, j = decode_from_state(h, 2, mealy)
            want = np.stack([i, j], axis=1)
            matches = [
                s for s in signed_perms
                if np.array_equal(decode_from_state_nd(h, 2, s, 2), want)
            ]
            assert len(matches) == 1, mealy
            state_of[mealy] = matches[0]
        assert len(set(state_of.values())) == 4
        for mealy, state in state_of.items():
            for digit in range(4):
                nxt = int(_DEC_NEXT[mealy, digit])
                assert child_state_nd(state, digit, 2) == state_of[nxt]
                q = int(_DEC_IJ[mealy, digit])
                assert child_corner_nd(state, digit, 2) == (q >> 1, q & 1)

    def test_canonical_start_state_2d_parity(self):
        # U for even depth, D for odd — the paper §4 rule
        u = canonical_start_state_nd(2, 2)
        d_ = canonical_start_state_nd(3, 2)
        assert u == identity_state_nd(2)
        assert d_ != u and canonical_start_state_nd(4, 2) == u
        assert canonical_start_state(2) != canonical_start_state(3)

    @pytest.mark.parametrize("d", [2, 3, 4])
    def test_first_child_rotation_has_order_d(self, d):
        # T_0 is the orientation rotation of order d (period-d cycling)
        t0 = child_transforms_nd(d)[0][1]
        g = identity_state_nd(d)
        for k in range(1, d + 1):
            g = hn.compose_state_nd(g, t0)
            assert (g == identity_state_nd(d)) == (k == d)

    def test_states_are_isometries(self):
        # every reachable state is a cube isometry: bijective on the cube
        # and preserving L1 distances (unit steps stay unit steps)
        levels = 2
        cube = hilbert_decode_raw_nd(np.arange(1 << (3 * levels)), 3, levels)
        for _, state in child_transforms_nd(3):
            out = apply_state_nd(state, cube, levels)
            assert len(set(map(tuple, out.tolist()))) == len(cube)
            d_in = np.abs(np.diff(cube, axis=0)).sum(axis=1)
            d_out = np.abs(np.diff(out, axis=0)).sum(axis=1)
            np.testing.assert_array_equal(d_in, d_out)


# ---------------------------------------------------------------------------
# Jump-over vs clip (the acceptance property)
# ---------------------------------------------------------------------------

class TestJumpOverVsClip:
    @pytest.mark.parametrize("d,hi", [(2, 40), (3, 14), (4, 7)])
    def test_randomized_shapes(self, d, hi):
        for shape in random_shapes(d, 12, hi) + [(1,) * d, (2,) * d]:
            got = fgf_nd.fgf_box_nd(shape)
            want = hn.clip_path_nd(hn.hilbert_decode_nd, shape)
            np.testing.assert_array_equal(got[:, 1:], want, err_msg=str(shape))
            np.testing.assert_array_equal(
                got[:, 0],
                hn.hilbert_encode_nd(want, hn.cover_bits(shape)),
                err_msg=str(shape),
            )

    def test_hilbert_path_nd_is_jump_over_and_identical(self):
        for shape in [(9, 9, 9), (5, 7, 3), (6, 6), (3, 3, 3, 3)]:
            np.testing.assert_array_equal(
                hn.hilbert_path_nd(shape),
                hn.clip_path_nd(hn.hilbert_decode_nd, shape),
            )

    def test_bit_identity_with_2d_fgf_walker(self):
        # the d-dim walker at d = 2 IS the paper's quadtree walker
        for n, m in [(5, 9), (12, 12), (7, 3), (16, 16), (1, 6)]:
            np.testing.assert_array_equal(
                fgf_nd.fgf_box_nd((n, m)),
                fgf.fgf_rect(fgf.cover_order(n, m), n, m),
            )
        for n in (5, 9, 12):
            np.testing.assert_array_equal(
                fgf_nd.fgf_triangle_nd((n, n)),
                fgf.fgf_triangle(fgf.cover_order(n), n=n),
            )

    @pytest.mark.parametrize("shape", [(6, 6, 6), (9, 9, 4), (5, 5, 5, 5)])
    def test_triangle_band_predicate_vs_filter(self, shape):
        d = len(shape)
        full = fgf_nd.fgf_box_nd(shape)
        tri = fgf_nd.fgf_triangle_nd(shape)
        np.testing.assert_array_equal(tri, full[full[:, 1] > full[:, 2]])
        loose = fgf_nd.fgf_triangle_nd(shape, strict=False)
        np.testing.assert_array_equal(loose, full[full[:, 1] >= full[:, 2]])
        upper = fgf_nd.fgf_triangle_nd(shape, lower=False)
        np.testing.assert_array_equal(upper, full[full[:, 1] < full[:, 2]])
        band = fgf_nd.fgf_path_nd(
            hn.cover_bits(shape), d,
            fgf_nd.IntersectRegion(
                fgf_nd.BandRegion(1), fgf_nd.BoxRegion(shape)
            ),
        )
        np.testing.assert_array_equal(
            band, full[np.abs(full[:, 1] - full[:, 2]) <= 1]
        )
        pred = fgf_nd.fgf_path_nd(
            hn.cover_bits(shape), d,
            fgf_nd.IntersectRegion(
                fgf_nd.PredicateRegion(lambda c: c.sum(axis=-1) % 3 == 0),
                fgf_nd.BoxRegion(shape),
            ),
        )
        np.testing.assert_array_equal(
            pred, full[full[:, 1:].sum(axis=1) % 3 == 0]
        )

    def test_empty_and_degenerate(self):
        assert fgf_nd.fgf_box_nd((0, 4)).shape == (0, 3)
        assert fgf_nd.fgf_box_nd((1, 1, 1)).shape == (1, 4)
        assert fgf_nd.fgf_triangle_nd((1, 1)).shape == (0, 3)
        with pytest.raises(ValueError):
            fgf_nd.fgf_path_nd(3, 1, fgf_nd.BoxRegion((4,)))
        with pytest.raises(ValueError):
            fgf_nd.fgf_path_nd(40, 3, fgf_nd.BoxRegion((4, 4, 4)))

    def test_counting_classifier_output_linear(self):
        # THE acceptance property: decode work scales with emitted cells,
        # not with the power-of-two cover volume
        for shape in [(9, 9, 9), (17, 17, 17), (9, 9, 9, 9), (129, 129)]:
            stats = {}
            out = fgf_nd.fgf_box_nd(shape, stats=stats)
            cover = (1 << hn.cover_bits(shape)) ** len(shape)
            assert stats["cells_decoded"] <= 3 * len(out), (shape, stats)
            assert stats["cells_decoded"] <= cover // 2, (shape, stats)
            assert stats["nodes_classified"] < cover // 8, (shape, stats)
        # the 2-D case the paper motivates: a thin boundary ring
        stats = {}
        out = fgf_nd.fgf_box_nd((1025, 1025), stats=stats)
        assert stats["cells_decoded"] <= 1.1 * len(out)
        assert stats["nodes_classified"] < 2048  # vs 4M cover cells


# ---------------------------------------------------------------------------
# Schedule-layer satellites
# ---------------------------------------------------------------------------

def _min_revisit_gap_ref(sched, axes):
    """The pre-vectorisation dict-loop implementation (oracle)."""
    s = np.asarray(sched, dtype=np.int64)
    last, best = {}, 0
    for step, key in enumerate(map(tuple, s[:, list(axes)])):
        if key in last:
            gap = step - last[key]
            if gap > 1 and (best == 0 or gap < best):
                best = gap
        last[key] = step
    return best


class TestScheduleSatellites:
    def test_min_revisit_gap_randomized(self):
        for _ in range(150):
            n = int(RNG.integers(0, 64))
            d = int(RNG.integers(2, 5))
            s = RNG.integers(0, 4, size=(n, d))
            k = int(RNG.integers(1, d + 1))
            axes = tuple(sorted(RNG.choice(d, size=k, replace=False).tolist()))
            assert min_revisit_gap(s, axes) == _min_revisit_gap_ref(s, axes)

    def test_min_revisit_gap_known_values(self):
        cube = tile_schedule_nd("hilbert", (8, 8, 8))
        assert min_revisit_gap(cube, (0, 1)) >= 3
        clipped = tile_schedule_nd("hilbert", (2, 2, 3))
        assert min_revisit_gap(clipped, (0, 1)) == 2

    def test_reuse_distances_definition(self):
        # stream: a b a c b a -> distances: -1 -1 1 -1 2 2
        d = reuse_distances(list("abacba"))
        np.testing.assert_array_equal(d, [-1, -1, 1, -1, 2, 2])

    def test_miss_counts_matches_lru_simulation(self):
        for _ in range(60):
            n = int(RNG.integers(0, 180))
            stream = [int(x) for x in RNG.integers(0, int(RNG.integers(1, 24)), size=n)]
            sizes = [1, 2, 3, 7, 16, 999]
            mc = miss_counts(stream, sizes)
            for c in sizes:
                assert mc[c] == lru_misses(stream, c), (stream, c)

    def test_miss_curve_single_pass_equivalence(self):
        sched = tile_schedule_nd("hilbert", (16, 16))
        sizes = (4, 12, 40)
        want = {c: lru_misses(pair_stream(sched), c) for c in sizes}
        assert miss_curve(sched, sizes) == want

    def test_triangle_schedule_nd_3d(self):
        t3 = triangle_schedule_nd("hilbert", (6, 6, 4))
        full = np.asarray(tile_schedule_nd("hilbert", (6, 6, 4)), np.int64)
        np.testing.assert_array_equal(t3, full[full[:, 0] > full[:, 1]])
        # non-hilbert curves filter their full schedule
        tz = triangle_schedule_nd("zorder", (5, 5, 3), strict=False)
        fz = np.asarray(tile_schedule_nd("zorder", (5, 5, 3)), np.int64)
        np.testing.assert_array_equal(tz, fz[fz[:, 0] >= fz[:, 1]])

    def test_triangle_schedule_2d_legacy_unchanged(self):
        # same contract the seed's fgf-based implementation satisfied
        t = triangle_schedule("hilbert", 12)
        assert len(t) == 12 * 11 // 2 and (t[:, 0] > t[:, 1]).all()
        np.testing.assert_array_equal(
            t, fgf.fgf_triangle(fgf.cover_order(12), n=12)[:, 1:]
        )


class TestBenchHarness:
    def test_run_json_zero_rows_exits_nonzero(self, tmp_path, monkeypatch):
        from benchmarks import run as bench_run

        out = tmp_path / "snap.json"
        # with the variable set, main() leaves JAX's cache directory as it
        # is: else every later compile in this process writes to the
        # checkout's .jax_cache, which test_compile_cache watches
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
        monkeypatch.setattr(
            "sys.argv", ["run.py", "nosuchbench", "--json", str(out)]
        )
        with pytest.raises(SystemExit) as e:
            bench_run.main()
        assert e.value.code == 1
        assert not out.exists()
