"""k-Means kernels with curve-scheduled tiles (paper §7).

Two generations of the same application:

* :func:`kmeans_assign_swizzled` — the assignment step alone.  It streams
  the (point_tile × centroid_tile) metric grid in curve order and emits
  *per-(point_tile, centroid_tile) partial results* — tile-local
  (min, argmin) of the reduced metric m(x,c) = ||c||² − 2⟨x,c⟩ — which
  ops.py merges with a tiny O(N · ct) jnp reduction.  Every output block
  is written exactly once, so the kernel is revisit-safe under ANY
  schedule order.  Retained as the multi-dispatch building block of the
  bit-exact Lloyd reference oracle.

* :func:`kmeans_lloyd_fused` — a FULL Lloyd iteration as ONE
  ``pallas_call`` (and the whole ``iters`` loop under ``jax.lax.scan``,
  so the kernel traces once).  The :func:`repro.core.kmeans_schedule`
  table drives two phases off the prefetched phase id (the PR-3
  phase-fusion recipe): phase 0 visits the (i, j) metric tiles in curve
  order and read-modify-writes a running (min, argmin) keyed by point
  tile in VMEM scratch (first-visit flags pick init vs merge; a point
  tile is revisited out of order, and the TPU pipeline never re-fetches
  a revisited output block), phase 1 re-streams each point tile once,
  writes its finished (min, argmin) row, and accumulates per-centroid
  partial sums/counts into a single resident output block.
  Per-iteration dispatches drop from 1 kernel + 2 ``segment_sum`` +
  host merge glue to exactly 1.

The kernels read the points feature-major, ``xT`` (D, N): a point tile
is a (D, bp) block whose lanes are points, so per-point results — the
metric's min and argmin over centroids, the assignment — come out as
lane-dense (1, bp) rows without a transpose.

Both paths share the tile math (:func:`_assign_tile`,
:func:`_update_tile`), so fused == reference is BIT-identical: min is
an exact reduction, the running merge's (value, index) tie-break
reproduces argmin's smallest-index rule under any visit order, and the phase-1 accumulation adds per-tile partials in
the same order the reference loop does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import as_choice, hilbert_sort_key, register_schedule_cache
from repro.core.program import CurveProgram
from repro.core.tracing import count, counters

from .launch import launch


def _quantise_points(
    x: jax.Array, *, nbits: int = 8, dims: int | None = None
) -> tuple[jax.Array, int]:
    """Min-max quantised integer grid of the first few features.

    Returns ``(q int32[N, d], effective_nbits)`` — the exact grid the
    Hilbert sort key is computed on, which is also the cache key of
    :func:`hilbert_point_order_cached`.
    """
    N, D = x.shape
    d = min(D, 3) if dims is None else min(dims, D)
    # largest per-axis bit depth whose canonical (multiple-of-d) rounding
    # keeps d*nbits <= 31 (int32 order values on device)
    cap = max((31 // d) // d * d, 1)
    nbits = min(nbits, cap)
    return hilbert_quantise(x, nbits=nbits, d=d), nbits


@functools.partial(jax.jit, static_argnames=("nbits", "d"))
def hilbert_quantise(x: jax.Array, *, nbits: int, d: int) -> jax.Array:
    """The device stage of :func:`_quantise_points`: its first ``d``
    features on the 2^nbits grid, int32[N, d]."""
    xf = x[:, :d].astype(jnp.float32)
    lo = jnp.min(xf, axis=0)
    hi = jnp.max(xf, axis=0)
    scale = ((1 << nbits) - 1) / jnp.maximum(hi - lo, 1e-9)
    return jnp.clip((xf - lo) * scale, 0, (1 << nbits) - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nbits",))
def hilbert_sort(q: jax.Array, *, nbits: int) -> jax.Array:
    """Permutation sorting the grid points ``q`` by their Hilbert key
    (a stable argsort)."""
    return jnp.argsort(hilbert_sort_key(q, nbits))


def hilbert_point_order(
    x: jax.Array, *, nbits: int = 8, dims: int | None = None
) -> jax.Array:
    """Permutation sorting points by their d-dimensional Hilbert key.

    The first ``dims`` features (default min(D, 3)) are min-max quantised
    to a 2^nbits grid and coded with the canonical d-dim Hilbert codec
    (:func:`repro.core.hilbert_sort_key`), so consecutive points — and
    therefore the point *tiles* the kernels stream — cover compact regions
    of feature space.  Used by the k-means and ε-join wrappers in ops.py.
    """
    q, nbits = _quantise_points(x, nbits=nbits, dims=dims)
    return hilbert_sort(q, nbits=nbits)


class _OrderCache:
    """Tiny LRU for point-order permutations, keyed on a digest of the
    quantised grid (keying on the raw N·d·4 grid bytes would pin them in
    host memory for the cache's lifetime).  Its hits and misses are the
    counters ``<name>.hits`` and ``<name>.misses``."""

    def __init__(self, name: str, maxsize: int = 64):
        self.maxsize = maxsize
        self._store: dict = {}
        self._names = (f"{name}.hits", f"{name}.misses")
        self._base = self._counts()  # the counters at the last clear

    def get(self, key, compute):
        hits, misses = self._names
        if key in self._store:
            count(hits)
            self._store[key] = self._store.pop(key)  # move to back (MRU)
            return self._store[key]
        count(misses)
        val = compute()
        self._store[key] = val
        if len(self._store) > self.maxsize:
            self._store.pop(next(iter(self._store)))
        return val

    def _counts(self) -> tuple[int, int]:
        c = counters()
        return tuple(c.get(n, 0) for n in self._names)

    def cache_clear(self):
        self._store.clear()
        self._base = self._counts()

    def cache_info(self):
        import collections

        info = collections.namedtuple("CacheInfo", "hits misses maxsize currsize")
        hits, misses = (v - b for v, b in zip(self._counts(), self._base))
        return info(hits, misses, self.maxsize, len(self._store))


# registered so core.schedule_cache_clear() drops it too (it caches on
# the quantised grid, which changes meaning when curves are re-registered)
_cached_order = register_schedule_cache(_OrderCache("order_cache"))


def _fmix32(h):
    """MurmurHash3's 32-bit finaliser: every input bit moves every
    output bit."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


@jax.jit
def grid_digest(q: jax.Array) -> jax.Array:
    """A 128-bit fingerprint of the grid ``q`` (int[N, d]), on the
    device: uint32[4], each the wrapping sum over the grid's cells of a
    mix of the cell's value and its position under its own seed.  Two
    grids of one shape that differ share it with probability about
    2^-128."""
    v = q.reshape(-1).astype(jnp.uint32)
    k = jnp.arange(v.shape[0], dtype=jnp.uint32)
    seeds = (0x9E3779B9, 0x7F4A7C15, 0x94D049BB, 0xBF58476D)
    return jnp.stack([
        jnp.sum(_fmix32(v ^ _fmix32(k + jnp.uint32(s))), dtype=jnp.uint32)
        for s in seeds
    ])


def hilbert_point_order_cached(
    x: jax.Array, *, nbits: int = 8, dims: int | None = None
) -> jax.Array:
    """:func:`hilbert_point_order` memoised on the quantised grid.

    The O(N log N) sort-key + argsort pipeline is a pure function of the
    quantised integer grid, so repeated calls on the same point set (every
    Lloyd iteration used to pay it; repeated ε-joins on one dataset still
    would) hit an LRU cache keyed on the grid's :func:`grid_digest`, so a
    lookup downloads 16 bytes, not the grid, and hashes nothing on the
    host.  Two grids sharing a digest would share a permutation, still
    one of each caller's N points, whose results stay exact.  Falls back
    to the uncached computation under tracing (no concrete digest to key
    on); bit-identical either way — same keys, same stable argsort.
    """
    if isinstance(x, jax.core.Tracer):
        return hilbert_point_order(x, nbits=nbits, dims=dims)
    q, nbits = _quantise_points(x, nbits=nbits, dims=dims)
    key = (np.asarray(grid_digest(q)).tobytes(), q.shape, nbits)
    return _cached_order.get(key, lambda: hilbert_sort(q, nbits=nbits))


# ---------------------------------------------------------------------------
# Shared tile math (kernel == reference, bit-identical in interpret mode)
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = (((1,), (1,)), ((), ()))  # contract the lane (point) axes: A @ B^T


def centroid_norms(c):
    """||c||² per centroid as a (K, 1) column — the kernels' third
    operand, computed once per iteration for the whole padded set."""
    c = c.astype(jnp.float32)
    return jnp.sum(c * c, axis=1, keepdims=True)


def _assign_tile(xTv, cv, cn, ct, *, bc: int, k_valid):
    """Tile-local (min metric, global argmin) rows for one metric tile.

    ``xTv`` is the (D, bp) feature-major point tile, ``cv`` the (bc, D)
    centroid tile and ``cn`` its (bc, 1) norms, ``ct`` its index (traced
    in the kernels, python int in host-side callers).  Returns two
    (1, bp) rows.
    """
    x = xTv.astype(jnp.float32)
    c = cv.astype(jnp.float32)
    # cross term c.x as D broadcast multiply-adds in feature order: exact
    # f32 semantics whatever the tile shape, on the chip and in the
    # interpreter alike (an MXU dot's summation order varies with the
    # tile's row count, which would make the K padding visible)
    cx = c[:, 0:1] * x[0:1, :]
    for d in range(1, c.shape[1]):
        cx = cx + c[:, d : d + 1] * x[d : d + 1, :]
    # metric tile m(c, x) = ||c||^2 - 2 c.x   (bc, bp); monotone in
    # distance per point
    m = cn - 2.0 * cx
    row = ct * bc + jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
    if k_valid is not None:
        # ragged K: pad centroids are plain zeros (magic 1e30 coordinates
        # would square to inf and breed NaNs in the metric); push them out
        # of the min/argmin with the largest finite f32 instead
        m = jnp.where(row < k_valid, m, jnp.float32(np.finfo(np.float32).max))
    tile_min = jnp.min(m, axis=0, keepdims=True)
    # argmin with the smallest-index tie-break, from the min itself
    big = jnp.int32(np.iinfo(np.int32).max)
    tile_arg = jnp.min(jnp.where(m == tile_min, row, big), axis=0, keepdims=True)
    return tile_min, tile_arg


def _update_tile(xTv, av, i, *, Kp: int, n_valid):
    """Per-centroid partials of one point tile: transposed sums
    (D, Kp) and counts (1, Kp).

    ``av`` is the (1, bp) row of global assignments, ``i`` the point
    tile index (for the ragged-N mask).  The one-hot matmul is the
    tile-math twin of ``segment_sum`` restricted to one tile.
    """
    bp = xTv.shape[1]
    cid = jax.lax.broadcasted_iota(jnp.int32, (Kp, bp), 0)
    onehot = (cid == av).astype(jnp.float32)  # (Kp, bp)
    if n_valid is not None:
        # ragged N: zero-pad points must not count toward any centroid
        col = i * bp + jax.lax.broadcasted_iota(jnp.int32, (Kp, bp), 1)
        onehot = jnp.where(col < n_valid, onehot, 0.0)
    part_sum = jax.lax.dot_general(
        xTv.astype(jnp.float32), onehot, _LANES,
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )
    part_cnt = jax.lax.dot_general(
        jnp.ones((1, bp), jnp.float32), onehot, _LANES,
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )
    return part_sum, part_cnt


def _phase_block(phase: int):
    """Index map of a per-point-tile output written only in ``phase``
    (1): elsewhere it points at the block the phase writes first, so the
    pipeline never writes back a block the kernel did not fill."""

    def index_map(s, sr):
        return (jnp.where(sr[s, 0] == phase, sr[s, 1], sr[0, 1]), 0, 0)

    return index_map


# ---------------------------------------------------------------------------
# Assignment-only kernel (multi-dispatch building block / reference)
# ---------------------------------------------------------------------------

def _assign_kernel(
    sched_ref, xT_ref, c_ref, cn_ref, min_out, arg_out, *, bc: int,
    k_valid: int | None,
):
    s = pl.program_id(0)
    tile_min, tile_arg = _assign_tile(
        xT_ref[...], c_ref[...], cn_ref[...], sched_ref[s, 1], bc=bc,
        k_valid=k_valid,
    )
    min_out[0, 0] = tile_min
    arg_out[0, 0] = tile_arg


@functools.partial(jax.jit, static_argnames=("bp", "bc", "k_valid", "interpret"))
def kmeans_assign_swizzled(
    schedule: jax.Array,
    x: jax.Array,
    c: jax.Array,
    *,
    bp: int = 256,
    bc: int = 128,
    k_valid: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(metric_min, assignment) per point.  x: (N, D), c: (K, D).

    N % bp == 0, K % bc == 0 (ops.py pads; ``k_valid`` is the true
    centroid count when K carries zero padding — pad columns are masked
    out of the min/argmin).  Returns
    (min_metric f32[N] — add ||x||² for true squared distances,
     assign int32[N]).
    """
    N, D = x.shape
    K, D2 = c.shape
    assert D == D2 and N % bp == 0 and K % bc == 0
    pt, ctn = N // bp, K // bc
    assert schedule.shape == (pt * ctn, 2)

    program = CurveProgram(
        name="kmeans_assign",
        schedule=schedule,
        kernel=functools.partial(_assign_kernel, bc=bc, k_valid=k_valid),
        in_specs=(
            pl.BlockSpec((D, bp), lambda s, sr: (0, sr[s, 0])),
            pl.BlockSpec((bc, D), lambda s, sr: (sr[s, 1], 0)),
            pl.BlockSpec((bc, 1), lambda s, sr: (sr[s, 1], 0)),
        ),
        out_specs=[
            pl.BlockSpec((1, 1, 1, bp), lambda s, sr: (sr[s, 0], sr[s, 1], 0, 0)),
            pl.BlockSpec((1, 1, 1, bp), lambda s, sr: (sr[s, 0], sr[s, 1], 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pt, ctn, 1, bp), jnp.float32),
            jax.ShapeDtypeStruct((pt, ctn, 1, bp), jnp.int32),
        ],
        columns=("i", "j"),
    )
    tile_min, tile_arg = launch(
        program, x.T, c, centroid_norms(c), interpret=interpret
    )
    tile_min, tile_arg = tile_min[:, :, 0], tile_arg[:, :, 0]

    # O(N * ct) merge of the per-centroid-tile partials
    best_ct = jnp.argmin(tile_min, axis=1)  # (pt, bp)
    min_m = jnp.min(tile_min, axis=1).reshape(N)
    arg = jnp.take_along_axis(tile_arg, best_ct[:, None, :], axis=1)[:, 0].reshape(N)
    return min_m, arg


# ---------------------------------------------------------------------------
# Fused Lloyd iteration: ONE pallas_call per iteration, scan over iters
# ---------------------------------------------------------------------------

def _lloyd_phases(
    sched_ref, xT_ref, c_ref, cn_ref, run_min, run_arg, *, bc, k_valid,
    write_row, update,
):
    """The two phases of one :func:`repro.core.kmeans_schedule` step,
    shared by the single-core and the shard-local Lloyd kernels.

    Phase 0 merges a running (min, arg) row per point tile in VMEM
    scratch — the (value, index) tie-break makes the merge
    order-independent AND equal to argmin's smallest-index rule.  Phase
    1 (every phase-0 visit of a tile precedes it) hands the finished row
    to ``write_row`` and the tile's update partials to ``update``.
    """
    s = pl.program_id(0)
    phase = sched_ref[s, 0]
    i = sched_ref[s, 1]
    j = sched_ref[s, 2]
    first = sched_ref[s, 3]
    row = pl.ds(i, 1)

    @pl.when(phase == 0)
    def _assign():
        tile_min, tile_arg = _assign_tile(
            xT_ref[...], c_ref[...], cn_ref[...], j, bc=bc, k_valid=k_valid
        )

        @pl.when(first == 1)
        def _init():
            run_min[row, :] = tile_min
            run_arg[row, :] = tile_arg

        @pl.when(first == 0)
        def _merge():
            cur_min = run_min[row, :]
            cur_arg = run_arg[row, :]
            better = (tile_min < cur_min) | (
                (tile_min == cur_min) & (tile_arg < cur_arg)
            )
            run_min[row, :] = jnp.where(better, tile_min, cur_min)
            run_arg[row, :] = jnp.where(better, tile_arg, cur_arg)

    @pl.when(phase == 1)
    def _update():
        arg = run_arg[row, :]
        write_row(run_min[row, :], arg)
        update(i, first, arg)


def _fused_lloyd_kernel(
    sched_ref, xT_ref, c_ref, cn_ref, min_ref, arg_ref, sum_ref, cnt_ref,
    run_min, run_arg, *, bc: int, Kp: int, k_valid: int | None,
    n_valid: int | None,
):
    """One fused Lloyd step (:func:`_lloyd_phases`): phase 1
    accumulates the per-centroid sums/counts into the single resident
    (D, Kp) / (1, Kp) output blocks."""

    def write_row(mn, arg):
        min_ref[0] = mn
        arg_ref[0] = arg

    def update(i, first, arg):
        part_sum, part_cnt = _update_tile(
            xT_ref[...], arg, i, Kp=Kp, n_valid=n_valid
        )

        @pl.when(first == 1)
        def _init():
            sum_ref[...] = part_sum
            cnt_ref[...] = part_cnt

        @pl.when(first == 0)
        def _acc():
            sum_ref[...] += part_sum
            cnt_ref[...] += part_cnt

    _lloyd_phases(
        sched_ref, xT_ref, c_ref, cn_ref, run_min, run_arg, bc=bc,
        k_valid=k_valid, write_row=write_row, update=update,
    )


def kmeans_lloyd_program(
    schedule, *, pt: int, ct: int, bp: int, bc: int, D: int,
    k_valid: int | None, n_valid: int | None, choice=None,
) -> CurveProgram:
    """The fused-Lloyd declaration (one iteration = one dispatch).

    Operands: the feature-major points ``xT`` (D, N), the centroids
    (Kp, D) and their :func:`centroid_norms`.  Streams (D, bp) point /
    (bc, D) centroid panels, keeps
    the running per-point-tile (min, argmin) rows in VMEM scratch
    (8 bytes per point), and accumulates into a single resident
    (D, Kp) + (1, Kp) f32 block pair — the residency the ops wrapper
    gates on.  Outputs: (min, arg) rows (pt, 1, bp) and the transposed
    sums (D, Kp) plus counts (1, Kp) — see :func:`lloyd_update`.

    ``choice`` (a ``kmeans``-kind :class:`repro.core.ScheduleChoice` or
    curve name) records which curve generated ``schedule``; the grid
    args ``(pt, ct)`` land in ``schedule_args`` so the table can be
    rebuilt under another curve at the ``with_schedule`` swap point.
    The schedule itself stays a caller-provided traced operand (it rides
    through ``jax.lax.scan``), so the recorded choice is metadata — the
    launcher only acts on it when explicitly asked to swap curves.
    """
    if choice is not None:
        choice = as_choice(choice, kind="kmeans").with_(
            block=(int(bp), int(bc))
        )
    Kp = ct * bc
    return CurveProgram(
        name="kmeans_lloyd_fused",
        schedule=schedule,
        kernel=functools.partial(
            _fused_lloyd_kernel, bc=bc, Kp=Kp, k_valid=k_valid, n_valid=n_valid
        ),
        in_specs=(
            pl.BlockSpec((D, bp), lambda s, sr: (0, sr[s, 1])),
            pl.BlockSpec((bc, D), lambda s, sr: (sr[s, 2], 0)),
            pl.BlockSpec((bc, 1), lambda s, sr: (sr[s, 2], 0)),
        ),
        out_specs=[
            pl.BlockSpec((1, 1, bp), _phase_block(1)),
            pl.BlockSpec((1, 1, bp), _phase_block(1)),
            pl.BlockSpec((D, Kp), lambda s, sr: (0, 0)),
            pl.BlockSpec((1, Kp), lambda s, sr: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pt, 1, bp), jnp.float32),
            jax.ShapeDtypeStruct((pt, 1, bp), jnp.int32),
            jax.ShapeDtypeStruct((D, Kp), jnp.float32),
            jax.ShapeDtypeStruct((1, Kp), jnp.float32),
        ],
        scratch_shapes=(
            pltpu.VMEM((pt, bp), jnp.float32),  # running min per point
            pltpu.VMEM((pt, bp), jnp.int32),  # running argmin per point
        ),
        phases=("assign", "update"),
        columns=("phase", "i", "j", "first_visit"),
        reference=lambda *a, **kw: kmeans_lloyd_reference(*a, **kw),
        choice=choice,
        schedule_args=(int(pt), int(ct)),
    )


def lloyd_update(c, sums_t, cnt):
    """New centroids from one iteration's transposed sums (D, Kp) and
    counts (1, Kp); empty clusters keep their centroid."""
    cw = cnt[0][:, None]
    return jnp.where(cw > 0, sums_t.T / jnp.maximum(cw, 1.0), c)


@functools.partial(
    jax.jit,
    static_argnames=("iters", "bp", "bc", "k_valid", "n_valid", "interpret"),
)
def kmeans_lloyd_fused(
    schedule: jax.Array,
    x: jax.Array,
    c0: jax.Array,
    *,
    iters: int,
    bp: int = 256,
    bc: int = 128,
    k_valid: int | None = None,
    n_valid: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``iters`` Lloyd iterations, ONE pallas dispatch each, under scan.

    schedule: the int32[pt*ct + pt, 4] :func:`repro.core.kmeans_schedule`
    table.  x: (N, D) with N % bp == 0; c0: (K, D) with K % bc == 0
    (ops.py pads; ``k_valid`` / ``n_valid`` are the true counts when the
    padding exists).  Returns (centroids f32[K, D], assign int32[N]).
    VMEM bound of the fused step: the resident accumulators are
    K*D + K f32, plus 8 bytes per point of running (min, argmin).
    """
    Np, D = x.shape
    Kp, D2 = c0.shape
    assert D == D2 and Np % bp == 0 and Kp % bc == 0
    pt, ct = Np // bp, Kp // bc
    steps = pt * ct + pt
    assert schedule.shape == (steps, 4), (schedule.shape, steps)

    program = kmeans_lloyd_program(
        schedule, pt=pt, ct=ct, bp=bp, bc=bc, D=D,
        k_valid=k_valid, n_valid=n_valid,
    )
    xT = x.T

    def step(carry, _):
        c, _assign = carry
        _min_m, arg, sums_t, cnt = launch(
            program, xT, c, centroid_norms(c), interpret=interpret
        )
        return (lloyd_update(c, sums_t, cnt), arg.reshape(Np)), None

    init = (c0.astype(jnp.float32), jnp.zeros((Np,), jnp.int32))
    (c, assign), _ = jax.lax.scan(step, init, None, length=iters)
    return c, assign


def _update_kernel(sched_ref, xT_ref, a_ref, sum_ref, cnt_ref, *, Kp, n_valid):
    s = pl.program_id(0)
    part_sum, part_cnt = _update_tile(
        xT_ref[...], a_ref[0], sched_ref[s, 0], Kp=Kp, n_valid=n_valid,
    )

    @pl.when(sched_ref[s, 1] == 1)
    def _init():
        sum_ref[...] = part_sum
        cnt_ref[...] = part_cnt

    @pl.when(sched_ref[s, 1] == 0)
    def _acc():
        sum_ref[...] += part_sum
        cnt_ref[...] += part_cnt


@functools.partial(
    jax.jit, static_argnames=("bp", "Kp", "n_valid", "interpret")
)
def kmeans_update_swizzled(
    schedule: jax.Array,
    x: jax.Array,
    assign: jax.Array,
    *,
    bp: int,
    Kp: int,
    n_valid: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-centroid (transposed sums f32[D, Kp], counts f32[1, Kp]) of an
    assignment.

    schedule: int32[pt, 2] rows ``(point_tile, first_visit)`` — the
    phase-1 slice of :func:`repro.core.kmeans_schedule`.  The standalone
    dispatch twin of the fused kernel's update phase (identical
    :func:`_update_tile` math, identical accumulation order), used by the
    Lloyd reference oracle in place of ``segment_sum`` so fused ==
    reference stays bit-identical.
    """
    Np, D = x.shape
    assert Np % bp == 0
    pt = Np // bp
    assert schedule.shape == (pt, 2)
    program = CurveProgram(
        name="kmeans_update",
        schedule=schedule,
        kernel=functools.partial(_update_kernel, Kp=Kp, n_valid=n_valid),
        in_specs=(
            pl.BlockSpec((D, bp), lambda s, sr: (0, sr[s, 0])),
            pl.BlockSpec((1, 1, bp), lambda s, sr: (sr[s, 0], 0, 0)),
        ),
        out_specs=[
            pl.BlockSpec((D, Kp), lambda s, sr: (0, 0)),
            pl.BlockSpec((1, Kp), lambda s, sr: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, Kp), jnp.float32),
            jax.ShapeDtypeStruct((1, Kp), jnp.float32),
        ],
        columns=("i", "first_visit"),
    )
    return launch(program, x.T, assign.reshape(pt, 1, bp), interpret=interpret)


def kmeans_lloyd_reference(
    schedule2d: jax.Array,
    update_schedule: jax.Array,
    x: jax.Array,
    c0: jax.Array,
    *,
    iters: int,
    bp: int = 256,
    bc: int = 128,
    k_valid: int | None = None,
    n_valid: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Multi-dispatch Lloyd oracle: per iteration one assignment
    ``pallas_call`` (per-tile partials + jnp merge glue) plus one
    :func:`kmeans_update_swizzled` accumulation ``pallas_call`` in the
    fused schedule's phase-1 order, so the result is BIT-identical to
    :func:`kmeans_lloyd_fused` in interpret mode.  The un-jitted python
    loop (2 dispatches + glue per iteration, host round-trip between
    iterations) is the baseline the fused path is benchmarked against.
    """
    Np, D = x.shape
    Kp = c0.shape[0]
    c = c0.astype(jnp.float32)
    assign = jnp.zeros((Np,), jnp.int32)
    for _ in range(iters):
        _min_m, assign = kmeans_assign_swizzled(
            schedule2d, x, c, bp=bp, bc=bc, k_valid=k_valid,
            interpret=interpret,
        )
        sums_t, cnt = kmeans_update_swizzled(
            update_schedule, x, assign, bp=bp, Kp=Kp, n_valid=n_valid,
            interpret=interpret,
        )
        c = lloyd_update(c, sums_t, cnt)
    return c, assign


# ---------------------------------------------------------------------------
# Shard-local Lloyd step (per-tile partials; the shard_map building block)
# ---------------------------------------------------------------------------

def kmeans_init(x: jax.Array, k: int, seed: int) -> jax.Array:
    """Initial centroids — shared by the single-core and sharded Lloyd
    paths so ``mesh=`` runs start from bit-identical c0.  Samples without
    replacement when possible; the degenerate k > N case falls back to
    sampling with replacement (duplicated centroids are harmless: the
    argmin tie-break keeps assignments deterministic and empty centroids
    retain their previous value)."""
    N = x.shape[0]
    key = jax.random.PRNGKey(seed)
    return x[jax.random.choice(key, N, shape=(k,), replace=k > N)]


def _shard_lloyd_kernel(
    sched_ref, xT_ref, c_ref, cn_ref, lim_ref, min_ref, arg_ref, sum_ref, cnt_ref,
    run_min, run_arg, *, bc: int, Kp: int,
):
    """One :func:`repro.core.kmeans_schedule` step on a shard's tiles.

    Identical phases to :func:`_fused_lloyd_kernel` (same
    :func:`_lloyd_phases`, same tile math), but phase 1 writes each
    point tile's *per-tile* partial (sums, counts) to its own output
    block instead of folding into a resident accumulator — the
    cross-shard fold happens outside the kernel in the single-core
    accumulation order (see kernels/sharded.py).  Ragged masks are
    *dynamic*: ``lim_ref`` is an int32[1, 2] ``(n_valid_local,
    k_valid)`` SMEM operand, so one traced program serves every shard of
    an SPMD ``shard_map`` (masking with the full extent is a bitwise
    no-op, which keeps padded and unpadded shards bit-identical to the
    statically-masked single-core kernel).
    """
    n_valid = lim_ref[0, 0]

    def write_row(mn, arg):
        min_ref[0] = mn
        arg_ref[0] = arg

    def update(i, first, arg):
        del first
        part_sum, part_cnt = _update_tile(
            xT_ref[...], arg, i, Kp=Kp, n_valid=n_valid
        )
        sum_ref[0] = part_sum
        cnt_ref[0] = part_cnt

    _lloyd_phases(
        sched_ref, xT_ref, c_ref, cn_ref, run_min, run_arg, bc=bc,
        k_valid=lim_ref[0, 1], write_row=write_row, update=update,
    )


def kmeans_shard_program(
    schedule, *, pt: int, ct: int, bp: int, bc: int, D: int
) -> CurveProgram:
    """Shard-local Lloyd-step declaration over a ``pt``-tile point shard.

    Outputs: (min, argmin) rows per point tile plus PER-TILE update
    partials ``sums f32[pt, D, Kp]`` (transposed) / ``counts
    f32[pt, 1, Kp]`` (each block written once, in phase 1).  Operands:
    the feature-major x shard (D, N_local), the replicated centroids and
    their norms, and the int32[1, 2] ``(n_valid_local, k_valid)`` limits row
    described in :func:`_shard_lloyd_kernel`.
    """
    Kp = ct * bc
    return CurveProgram(
        name="kmeans_shard_step",
        schedule=schedule,
        kernel=functools.partial(_shard_lloyd_kernel, bc=bc, Kp=Kp),
        in_specs=(
            pl.BlockSpec((D, bp), lambda s, sr: (0, sr[s, 1])),
            pl.BlockSpec((bc, D), lambda s, sr: (sr[s, 2], 0)),
            pl.BlockSpec((bc, 1), lambda s, sr: (sr[s, 2], 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_specs=[
            pl.BlockSpec((1, 1, bp), _phase_block(1)),
            pl.BlockSpec((1, 1, bp), _phase_block(1)),
            pl.BlockSpec((1, D, Kp), _phase_block(1)),
            pl.BlockSpec((1, 1, Kp), _phase_block(1)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pt, 1, bp), jnp.float32),
            jax.ShapeDtypeStruct((pt, 1, bp), jnp.int32),
            jax.ShapeDtypeStruct((pt, D, Kp), jnp.float32),
            jax.ShapeDtypeStruct((pt, 1, Kp), jnp.float32),
        ],
        scratch_shapes=(
            pltpu.VMEM((pt, bp), jnp.float32),
            pltpu.VMEM((pt, bp), jnp.int32),
        ),
        phases=("assign", "update"),
        columns=("phase", "i", "j", "first_visit"),
        reference=lambda *a, **kw: kmeans_lloyd_fused(*a, **kw),
    )
