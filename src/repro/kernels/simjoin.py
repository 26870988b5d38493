"""ε-similarity-join kernels with FGF jump-over scheduling (paper §7, [20]).

The join enumerates unordered point pairs with ‖x_i − x_j‖ ≤ ε.  Only the
lower-triangular (i_tile ≥ j_tile) half of the tile grid carries work,
and of it only the tile pairs whose bounding boxes lie within ε:
:func:`simjoin_schedule` tests every such pair on the device and lists
the ones it keeps in the order the curve visits them (for ``hilbert``
the true Hilbert order value of each tile pair, as the FGF-Hilbert
walker enumerates the triangle), so no host loop over tile pairs is
left on the join's path.

Point ordering: the join benefits doubly from Hilbert machinery — the
curve orders the *tiles*, and :func:`repro.kernels.kmeans.
hilbert_point_order` (d-dimensional ``hilbert_sort_key``) can pre-sort
the *points* so ε-neighbours concentrate near the tile-grid diagonal
(``hilbert_order=True`` in ops.py), which is also what makes the
tiles' boxes small and the pruned schedule short.

Two passes, one hit predicate (:func:`_hit_tile`, shared so counts and
emitted pairs can never disagree).  The kernels read the i tile
point-major (bp, D) and the j tile feature-major (D, bp), so the
distance tile needs no transpose and per-point sums come out as
lane-dense rows:

* :func:`simjoin_tile_hits_swizzled` — per-step partial row/column hit
  sums (each output block written exactly once → safe under any
  schedule); ops.py scatter-adds them onto the point axis for
  ``simjoin_counts``, and their row-sum per step is the per-tile hit
  total that sizes pair emission.
* :func:`simjoin_emit_swizzled` — pass 2: for the tiles pass 1 found
  non-empty, each grid step packs its tile's rows in VMEM
  (:func:`_pack_rows`: row r's hit columns, ascending, in lanes
  ``[0, n_r)``) and writes them with the row counts ``n_r`` to its own
  output blocks (write-once, order-free).

Both passes run in fixed blocks (:func:`simjoin_pairs_scheduled`): pass
1 in dispatches of one compiled size of at most :data:`PASS1_STEPS`
steps, whose schedule slice fits SMEM and whose grid runs only the
block's live steps, and pass 2 in dispatches of at most
:data:`PASS2_CELLS` mask cells, each flattened into its share of the
one output before the next, so the join has no size limit short of its
int32 pair offsets.

The compaction is two-level and count-directed: the emit kernel packs
each row, then the flatten kernel (:func:`simjoin_flatten_program`,
inside :func:`simjoin_compact`) writes each listed tile's pairs at the
tile's pair offset, in schedule-then-row-major order, the order of the
tiles' rows.  A step lays its tile out as one run in VMEM with the
shift network of the emit kernel, over the run's flat lanes, at the
narrowest run width that holds the tile's fullest row (one (8, 128)
register while no row holds more than four hits), moves it behind the partial
128-lane row carried from the step before, and DMAs only whole, aligned
rows to the output; the last step writes the carried row.  The ids are
then mapped back through the point order with two 1-D gathers.  No
cell-level ``jnp.nonzero``: in XLA it is a scatter-add with one update
per mask cell (and a sort once its bins outgrow the chip's memory),
while only 0.1–1% of the cells of a non-empty tile hold a pair.
Gathers take 1-D indices only: XLA's TPU compile of a gather indexed by
``(P, 2)`` takes minutes at millions of pairs.  Sorting inside the
kernel has no TPU lowering.

A diagonal tile counts each unordered pair once via a strict i<j mask; an
off-diagonal (i_tile > j_tile) tile contributes row sums to the i side
and column sums to the j side, and emits (global_i, global_j) with
global_i > global_j always.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import as_choice, register_schedule_cache, tile_schedule_nd
from repro.core.jax_hilbert import hilbert_encode_jax
from repro.core.program import CurveProgram
from repro.core.tracing import count, span

from .launch import launch, resolve_interpret, sync_copy

# Pass-1 steps a dispatch: the schedule slice is scalar-prefetched into
# SMEM (1 MiB) at 8 bytes a step, so 2^16 steps take 512 KiB.
PASS1_STEPS = 1 << 16
# Mask cells a pass-2 dispatch packs: 2^29 (8192 tiles of 256², 512 MB
# of int8 ids), which bounds the device memory a block's ids hold.
PASS2_CELLS = 1 << 29


def check_pair_offsets(P_total: int, bp: int) -> None:
    """Raise if the join's pair total would overflow int32 (``P + bp²``
    must stay int32-addressable).  A raised :class:`ValueError`, not
    ``assert`` — the guard must survive ``python -O``.  Shared by the
    single-core and both sharded emission paths."""
    if P_total + bp * bp >= 2**31:
        raise ValueError(
            f"pair count {P_total} overflows the int32 offsets "
            f"(P + bp^2 must stay below 2^31); reduce eps or join in "
            f"chunks"
        )


@jax.jit
def simjoin_permute(x: jax.Array, perm: jax.Array) -> jax.Array:
    """The points in the order ``perm`` (the join's Hilbert order)."""
    return x[perm]


_LANES = (((1,), (1,)), ((), ()))  # contract the lane axes: A @ B^T


def _hit_tile(xiv, xjTv, ti, tj, *, eps2: float, n_valid):
    """Boolean (bp, bp) hit mask of tile pair (ti, tj), pairs counted once.

    ``xiv`` is the (bp, D) i tile, ``xjTv`` the feature-major (D, bp) j
    tile.  Shared by the count and emit kernels — the single source of
    truth for what an ε-hit is (threshold form, diagonal strictness,
    ragged-N masking), so pass-1 totals always equal pass-2 emission
    counts.
    """
    xi = xiv.astype(jnp.float32)  # (bp, d)
    xjT = xjTv.astype(jnp.float32)  # (d, bp)
    d2 = (
        jnp.sum(xi * xi, axis=1, keepdims=True)
        - 2.0 * jnp.dot(
            xi, xjT, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        + jnp.sum(xjT * xjT, axis=0, keepdims=True)
    )
    hit = d2 <= eps2
    ii = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    hit = jnp.logical_and(hit, (ii > jj) | (ti != tj))
    if n_valid is not None:
        # ragged N: the pad rows are plain zeros (which WOULD ε-join each
        # other — and huge magic values would overflow f32); mask them by
        # global point index instead of poisoning the coordinates
        bp = hit.shape[0]
        gi = ti * bp + ii
        gj = tj * bp + jj
        hit = jnp.logical_and(hit, (gi < n_valid) & (gj < n_valid))
    return hit


def _hit_sums(hit):
    """(row sums over j, column sums over i) of a hit tile as (1, bp)
    int32 rows — ones-vector matmuls on the MXU, exact for 0/1 terms."""
    h = hit.astype(jnp.float32)
    ones = jnp.ones((1, h.shape[0]), jnp.float32)
    rows = jax.lax.dot_general(ones, h, _LANES, preferred_element_type=jnp.float32)
    cols = jnp.dot(ones, h, preferred_element_type=jnp.float32)
    return rows.astype(jnp.int32), cols.astype(jnp.int32)


def _join_kernel(
    sched_ref, xi_ref, xjT_ref, hi_out, hj_out, *, eps2: float,
    n_valid: int | None,
):
    s = pl.program_id(0)
    hit = _hit_tile(
        xi_ref[...], xjT_ref[...], sched_ref[s, 0], sched_ref[s, 1],
        eps2=eps2, n_valid=n_valid,
    )
    hi_out[0], hj_out[0] = _hit_sums(hit)


@functools.partial(jax.jit, static_argnames=("eps", "bp", "n_valid", "interpret"))
def simjoin_tile_hits_swizzled(
    schedule: jax.Array,
    x: jax.Array,
    *,
    eps: float,
    bp: int = 256,
    n_valid: int | None = None,
    interpret: bool = False,
    live=None,
) -> tuple[jax.Array, jax.Array]:
    """Per-step partial hit sums: (row_hits, col_hits), each int32[steps, bp].

    schedule: int32[steps, 2] of lower-triangle (i_tile >= j_tile) tile
    pairs (any order; FGF-Hilbert by default via ops.py).
    x: (N, D) with N % bp == 0.  ``row_hits[s].sum()`` is the number of
    unordered pairs found in step ``s``'s tile — pass 1 of pair emission.
    ``live`` (traced, optional): the grid runs the first ``live`` steps
    only, and the rows past them are never written.
    """
    N, D = x.shape
    assert N % bp == 0
    program = simjoin_hits_program(
        schedule, eps=eps, bp=bp, D=D, n_valid=n_valid
    )
    if live is not None:
        program = dataclasses.replace(program, grid=(live,))
    hi, hj = launch(program, x, x.T, interpret=interpret)
    return hi[:, 0], hj[:, 0]


def simjoin_hits_program(
    schedule, *, eps: float, bp: int, D: int, n_valid: int | None,
    choice=None,
) -> CurveProgram:
    """Pass-1 declaration: one (1, bp) row/col partial pair per schedule
    step (operands: points (N, D) and their transpose (D, N)), each
    written exactly once — safe under any order, so the SAME
    program serves the single-core triangle schedule and each shard's
    curve-range slice of it (kernels/sharded.py).  ``choice`` (a
    ``triangle``-kind :class:`repro.core.ScheduleChoice` or curve name)
    records which curve ordered the tile pairs — metadata for the
    program signature; the join's curve axis is resolved upstream in
    ops.py because the two-pass driver host-syncs between dispatches."""
    if choice is not None:
        choice = as_choice(choice, kind="triangle").with_(block=(int(bp),))
    steps = schedule.shape[0]
    return CurveProgram(
        name="simjoin_hits",
        schedule=schedule,
        choice=choice,
        kernel=functools.partial(
            _join_kernel, eps2=float(eps) ** 2, n_valid=n_valid
        ),
        in_specs=_tile_pair_specs(bp, D, 0, 1),
        out_specs=[
            pl.BlockSpec((1, 1, bp), lambda s, sr: (s, 0, 0)),
            pl.BlockSpec((1, 1, bp), lambda s, sr: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((steps, 1, bp), jnp.int32),
            jax.ShapeDtypeStruct((steps, 1, bp), jnp.int32),
        ],
        columns=("i", "j"),
    )


def _tile_pair_specs(bp: int, D: int, ci: int, cj: int):
    """Block specs of the (x, xT) operand pair: the i tile (bp, D) from
    schedule column ``ci``, the feature-major j tile (D, bp) from ``cj``."""
    return (
        pl.BlockSpec((bp, D), lambda s, sr: (sr[s, ci], 0)),
        pl.BlockSpec((D, bp), lambda s, sr: (0, sr[s, cj])),
    )


def _join_rows_kernel(
    sched_ref, xi_ref, xjT_ref, hi_out, *, eps2: float, n_valid: int | None,
    gi_col: int, gj_col: int,
):
    s = pl.program_id(0)
    hit = _hit_tile(
        xi_ref[...], xjT_ref[...], sched_ref[s, gi_col], sched_ref[s, gj_col],
        eps2=eps2, n_valid=n_valid,
    )
    hi_out[0] = _hit_sums(hit)[0]


def simjoin_hits_rows_program(
    schedule, *, eps: float, bp: int, D: int, n_valid: int | None,
    halo: bool = False,
) -> CurveProgram:
    """Pass-1 declaration emitting ONLY the per-step row sums — whose
    totals pick the tiles pair emission visits.  The sharded wrapper uses this instead
    of :func:`simjoin_hits_program` so the shard_map never materialises
    (or transfers) the unused column partials.

    Operands: the points and their transpose.  ``halo=False``: 2-col
    ``(i, j)`` schedule over one global point buffer.  ``halo=True``:
    4-col ``(i_slot, j_slot, i, j)`` schedule over a shard's
    resident+halo buffer — the *slot* columns drive the
    BlockSpec index maps (where a tile lives in the local buffer), the
    *global* tile ids drive :func:`_hit_tile`'s diagonal strictness and
    ragged-N masking, which are defined on global point indices.
    """
    steps = schedule.shape[0]
    gi_col, gj_col = (2, 3) if halo else (0, 1)
    return CurveProgram(
        name="simjoin_hits_rows",
        schedule=schedule,
        kernel=functools.partial(
            _join_rows_kernel, eps2=float(eps) ** 2, n_valid=n_valid,
            gi_col=gi_col, gj_col=gj_col,
        ),
        in_specs=_tile_pair_specs(bp, D, 0, 1),
        out_specs=pl.BlockSpec((1, 1, bp), lambda s, sr: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, 1, bp), jnp.int32),
        columns=("i_slot", "j_slot", "i", "j") if halo else ("i", "j"),
    )


@functools.partial(
    jax.jit, static_argnames=("steps", "eps", "bp", "n_valid", "interpret")
)
def simjoin_totals(
    schedule: jax.Array,
    start,
    live,
    x: jax.Array,
    *,
    steps: int,
    eps: float,
    bp: int,
    n_valid: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One block of pass 1 of pair emission: the number of unordered
    pairs in the tiles of schedule rows ``[start, start + live)``,
    int32[steps], zero past ``live`` (1 <= live <= steps).  The block
    prefetches ``steps`` rows and its grid runs ``live`` steps: both
    ``start`` and ``live`` are traced, so every block of one size is one
    compiled program, and a short last block costs only its own steps."""
    block = jax.lax.dynamic_slice_in_dim(schedule, start, steps, axis=0)
    hits_i, _ = simjoin_tile_hits_swizzled(
        block, x, eps=eps, bp=bp, n_valid=n_valid, interpret=interpret,
        live=live,
    )
    return jnp.where(jnp.arange(steps) < live, jnp.sum(hits_i, axis=1), 0)


@functools.partial(jax.jit, static_argnames=("eps", "bp", "n_valid", "interpret"))
def simjoin_counts_swizzled(
    schedule: jax.Array,
    x: jax.Array,
    *,
    eps: float,
    bp: int = 256,
    n_valid: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Neighbour count per point for the ε-join over unordered pairs.

    Scatter-adds the per-step partials of
    :func:`simjoin_tile_hits_swizzled` onto the point axis.  Returns
    int32[N] counts (self excluded).
    """
    N, D = x.shape
    pt = N // bp
    hits_i, hits_j = simjoin_tile_hits_swizzled(
        schedule, x, eps=eps, bp=bp, n_valid=n_valid, interpret=interpret
    )
    counts = jnp.zeros((pt, bp), dtype=jnp.int32)
    counts = counts.at[schedule[:, 0]].add(hits_i)
    counts = counts.at[schedule[:, 1]].add(hits_j)
    return counts.reshape(N)


# ---------------------------------------------------------------------------
# The schedule: lower-triangle tile pairs whose boxes lie within ε
# ---------------------------------------------------------------------------

def _tile_boxes(xp, bp: int, n_valid: int | None):
    """Per tile of ``bp`` rows: the bounding box ``(lo, hi)`` f32[pt, D]
    of its valid rows (pad rows left out) and the largest squared norm
    among them, f32[pt]."""
    Np, D = xp.shape
    xt = xp.astype(jnp.float32).reshape(Np // bp, bp, D)
    valid = (jnp.arange(Np) < (Np if n_valid is None else n_valid)).reshape(
        Np // bp, bp, 1
    )
    lo = jnp.min(jnp.where(valid, xt, jnp.inf), axis=1)
    hi = jnp.max(jnp.where(valid, xt, -jnp.inf), axis=1)
    sq = jnp.max(jnp.where(valid[..., 0], jnp.sum(xt * xt, axis=2), 0.0), axis=1)
    return lo, hi, sq


def _reach(xp, *, eps: float, bp: int, n_valid: int | None):
    """bool[pt, pt]: lower-triangle tile pairs (i >= j) whose bounding
    boxes lie within ε, and so every pair the kernels can count.

    The box gap takes the relative f32 slack of the sharded reach test,
    plus a bound on the rounding of the kernels' f32 distance ``|a|² −
    2a·b + |b|²``, which grows with the points' squared norms: a pair
    the kernel would count always lies in a kept tile pair."""
    lo, hi, sq = _tile_boxes(xp, bp, n_valid)
    pt, D = lo.shape
    g2 = jnp.zeros((pt, pt), jnp.float32)
    for d in range(D):
        g = jnp.maximum(
            jnp.maximum(lo[:, None, d] - hi[None, :, d], lo[None, :, d] - hi[:, None, d]),
            0.0,
        )
        g2 = g2 + g * g
    eps_eff = float(eps) * (1.0 + 1e-5) + 1e-6
    thr = eps_eff * eps_eff + (2 * D + 6) * 2.0**-23 * (sq[:, None] + sq[None, :])
    i = jax.lax.broadcasted_iota(jnp.int32, (pt, pt), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (pt, pt), 1)
    return (i >= j) & (g2 <= thr)


@register_schedule_cache
@functools.lru_cache(maxsize=1)
def _curve_rank(curve: str, pt: int) -> jax.Array:
    """int32[pt·pt]: each tile pair's position on ``curve``'s walk of
    the pt×pt grid, whose lower triangle is the triangle schedule.

    For the curves other than ``hilbert``, which has a device encoder.
    The walk is built on the host once per (curve, pt), O(pt²): 61 M
    cells and 244 MB on the device at 2,000,000 points of bp = 256, so
    only the latest table is kept."""
    path = np.asarray(tile_schedule_nd(curve, (pt, pt)), dtype=np.int64)
    rank = np.empty(pt * pt, np.int32)
    rank[path[:, 0] * pt + path[:, 1]] = np.arange(pt * pt, dtype=np.int32)
    return jnp.asarray(rank)


@functools.partial(jax.jit, static_argnames=("eps", "bp", "n_valid", "cap"))
def simjoin_schedule(xp, rank, *, eps: float, bp: int, n_valid: int | None, cap: int):
    """The pruned schedule of the points ``xp``: ``(sched int32[cap, 2],
    kept)``, the ``kept`` tile pairs of :func:`_reach` in curve order
    (``rank``, or the Hilbert order value of ``(i, j)`` where ``rank`` is
    None), then rows of zeros.  ``cap=0`` gives only ``kept``, which
    sizes ``cap``.

    Pair k lies in the row i whose kept pairs start at or before k, as
    the kth hit past that start: a spread over the rows' offsets and a
    binary search over each row's running count, never a compaction of
    the pt² cells."""
    keep = _reach(xp, eps=eps, bp=bp, n_valid=n_valid)
    kept = jnp.sum(keep, dtype=jnp.int32)
    if cap == 0:
        return jnp.zeros((0, 2), jnp.int32), kept
    pt = keep.shape[0]
    run = jnp.cumsum(keep.astype(jnp.int32), axis=1)  # hits up to (i, j)
    n_row = run[:, -1]
    start = jnp.cumsum(n_row) - n_row
    i = _spread(start, jnp.arange(pt, dtype=jnp.int32), cap)
    want = jnp.arange(cap, dtype=jnp.int32) - start[i] + 1
    # j = the count of columns whose running count is still below want
    j = jnp.zeros(cap, jnp.int32)
    for b in reversed(range(max(pt - 1, 1).bit_length())):
        probe = j + (1 << b)
        below = run.reshape(-1)[i * pt + jnp.minimum(probe, pt) - 1] < want
        j = jnp.where((probe <= pt) & below, probe, j)
    j = jnp.minimum(j, pt - 1)
    if rank is None:
        key = hilbert_encode_jax(i, j, max(pt - 1, 1).bit_length())
    else:
        key = rank[i * pt + j]
    live = jnp.arange(cap, dtype=jnp.int32) < kept
    key = jnp.where(live, key, jnp.iinfo(jnp.int32).max)
    # the cell i·pt + j rides along (pt² < 2^31, as `run`'s flat index needs)
    _, ij = jax.lax.sort((key, jnp.where(live, i * pt + j, 0)), num_keys=1)
    return jnp.stack([ij // pt, ij % pt], axis=1), kept


def _schedule_cap(kept: int) -> int:
    """Rows of the pruned schedule: the power of two at or above 5/4 of
    ``kept``, each size a compile.  A join's kept count moves with the
    order of its points by a fraction of a percent, so a count just under
    a power of two (a 2,000,000-point join keeps 130-131 k tile pairs;
    2^17 = 131,072) would switch sizes from join to join; with the
    headroom it takes the larger one every time."""
    return _bucket(kept + kept // 4)


def pruned_schedule(
    xp: jax.Array, *, eps: float, bp: int, n_valid: int | None, curve: str,
) -> tuple[jax.Array, int]:
    """The tile pairs of ``xp`` (Np % bp == 0) a join has to visit, on
    the device: ``(sched, kept)`` as :func:`simjoin_schedule` gives them,
    with ``cap`` from :func:`_schedule_cap` (``kept`` is the one sync)."""
    pt = xp.shape[0] // bp
    rank = None if curve == "hilbert" else _curve_rank(curve, pt)
    kw = dict(eps=float(eps), bp=bp, n_valid=n_valid)
    kept = int(simjoin_schedule(xp, rank, cap=0, **kw)[1])
    sched, _ = simjoin_schedule(xp, rank, cap=_schedule_cap(kept), **kw)
    return sched, kept


# ---------------------------------------------------------------------------
# Pass 2: each hit tile's rows packed in the kernel, then flattened by tiles
# ---------------------------------------------------------------------------

def _id_code(bp: int):
    """(dtype, bias) of a packed column id: int8 ``j - 128`` while the
    ids fit (bp <= 256, no larger than a hit mask), else int32 ``j``."""
    return (jnp.int8, 128) if bp <= 256 else (jnp.int32, 0)


def _pack_rows(hit):
    """Each row's hit columns moved to its lanes ``[0, n_r)`` in
    ascending order, as int32 column ids (junk beyond ``n_r``).

    A hit at column j moves left by its count of preceding misses,
    ``d = j - c``, where ``c`` (the hits before j) is one bf16 matmul
    against strictly-upper ones, exact in f32 for counts <= bp.  The
    move is a shift network: step b rolls the lanes left by 2^b and
    takes the elements whose ``d`` has bit b set.  Taking the bits from
    low to high, a moving element sits at lane >= 2^b, so nothing wraps
    and no two elements meet.  Each lane carries its element's ``d``
    (-1: empty), and the column id is its final lane plus ``d``."""
    bp = hit.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 1)
    )
    c = jnp.dot(
        hit.astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    d = jnp.where(hit, lane - c, -1)
    b = 1
    while b < bp:
        moved = pltpu.roll(d, bp - b, 1)  # moved[l] = d[l + b]
        arrives = (moved >= 0) & ((moved & b) != 0)
        stays = (d >= 0) & ((d & b) == 0)
        d = jnp.where(arrives, moved, jnp.where(stays, d, -1))
        b *= 2
    return lane + d


def _emit_kernel(
    sched_ref, xi_ref, xjT_ref, ids_out, n_out, *, eps2: float,
    n_valid: int | None, gi_col: int, gj_col: int, live_col: int,
):
    s = pl.program_id(0)
    hit = _hit_tile(
        xi_ref[...], xjT_ref[...], sched_ref[s, gi_col], sched_ref[s, gj_col],
        eps2=eps2, n_valid=n_valid,
    )
    hit = jnp.logical_and(hit, sched_ref[s, live_col] == 1)
    _, bias = _id_code(hit.shape[1])
    ids_out[0] = (_pack_rows(hit) - bias).astype(ids_out.dtype)
    n_out[0] = _hit_sums(hit)[0]


def simjoin_emit_program(
    table, *, eps: float, bp: int, D: int, n_valid: int | None,
    halo: bool = False, choice=None,
) -> CurveProgram:
    """Pass-2 declaration: per table row, its tile's hit rows packed
    (``(bp, bp)`` column ids, :func:`_id_code`) and their hit counts
    (a ``(1, bp)`` int32 row), each block written once.
    ``halo=False``: 3-col rows ``(i, j, live)`` over one point buffer;
    ``halo=True``: 5-col rows ``(i_slot, j_slot, i, j, live)`` over a
    shard's resident+halo buffer (slots drive the index maps, global ids
    the hit predicate).  ``live == 0`` rows are SPMD / bucket padding
    and count no hits.  Operands: the points and their transpose."""
    if choice is not None:
        choice = as_choice(choice, kind="triangle").with_(block=(int(bp),))
    steps = table.shape[0]
    gi_col, gj_col, live_col = (2, 3, 4) if halo else (0, 1, 2)
    return CurveProgram(
        name="simjoin_emit_halo" if halo else "simjoin_emit",
        schedule=table,
        choice=choice,
        kernel=functools.partial(
            _emit_kernel, eps2=float(eps) ** 2, n_valid=n_valid,
            gi_col=gi_col, gj_col=gj_col, live_col=live_col,
        ),
        in_specs=_tile_pair_specs(bp, D, 0, 1),
        out_specs=[
            pl.BlockSpec((1, bp, bp), lambda s, sr: (s, 0, 0)),
            pl.BlockSpec((1, 1, bp), lambda s, sr: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((steps, bp, bp), _id_code(bp)[0]),
            jax.ShapeDtypeStruct((steps, 1, bp), jnp.int32),
        ],
        columns=(
            ("i_slot", "j_slot", "i", "j", "live") if halo
            else ("i", "j", "live")
        ),
    )


@functools.partial(jax.jit, static_argnames=("eps", "bp", "n_valid", "interpret"))
def simjoin_emit_swizzled(
    table: jax.Array,
    x: jax.Array,
    *,
    eps: float,
    bp: int,
    n_valid: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Packed hit rows ``(ids[rows, bp, bp], counts int32[rows, 1, bp])``
    of the ``(i_tile, j_tile, live)`` rows of ``table`` (ops.py passes
    the tiles pass 1 found non-empty, padded to a power-of-two row count
    with ``live=0``).  :func:`pairs_from_masks` turns them into pairs."""
    N, D = x.shape
    assert N % bp == 0
    program = simjoin_emit_program(table, eps=eps, bp=bp, D=D, n_valid=n_valid)
    return tuple(launch(program, x, x.T, interpret=interpret))


def _bucket(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _spread(starts, values, P: int):
    """``values[..., q]`` at every k in ``[starts[q], starts[q + 1])``,
    int[..., P], for sorted ``starts`` (those >= P drop out).  Each q
    scatters its step from the value before it at its start, and a
    cumsum adds the steps up: those up to q telescope to q's value."""
    steps = jnp.diff(values, axis=-1, prepend=0)
    out = jnp.zeros(values.shape[:-1] + (P,), values.dtype)
    out = out.at[..., starts].add(steps, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(out, axis=-1)


def _flatten_widths(bp: int) -> tuple[int, int, tuple[int, ...]]:
    """``(bq, S, widths)`` of the flatten kernel for tiles of ``bp``
    rows: ``bq`` the tile padded to a power of two of at least 128, ``S``
    its bits (a column id's field), and the run widths ``M`` it compiles,
    smallest first.  A tile whose rows hold at most ``M`` hits each is
    laid out as ``bq`` rows of ``M`` lanes, ``bq·M/128`` rows of 128: the
    smallest width fills one (8, 128) register, each next one is four
    times as wide, and the last holds a full row."""
    bq = max(128, _bucket(bp))
    if bq > 1024:  # (d, flag, column) must fit one int32
        raise ValueError(f"the flatten takes tiles of at most 1024 rows, not {bp}")
    widths = [max(1, 1024 // bq)]
    while widths[-1] < bp:
        widths.append(min(4 * widths[-1], bq))
    return bq, bq.bit_length() - 1, tuple(widths)


def _tile_run(vals, v_ref, f_ref, M: int, S: int):
    """The tile's pairs as one run in row-major order: ``(R, 128)``
    int32, ``R = bq·M/128``, run position k at ``(k // 128, k % 128)``.

    ``vals`` (bp, >= M lanes) holds row r's hits in lanes ``[0, M)``
    packed as ``(d << S + 1) | 1 << S | column``, ``d = r·M - o_r`` (``o``
    the rows' exclusive cumsum), zero past ``n_r``.  Laid out as ``bq``
    rows of ``M`` lanes (``v_ref`` or ``f_ref``, zero past the tile),
    hit ``(r, p)`` sits at ``r·M + p`` and belongs at ``o_r + p``, so it
    moves left by ``d``, which never falls along the run: the shift
    network of :func:`_pack_rows`, over the run's flat lanes, low bits
    first, moves it without collisions."""
    bp = vals.shape[0]
    bq = v_ref.shape[0]
    R = bq * M // 128
    if M <= 128:
        # run row q, lanes [g·M, g·M + M) <- tile row q·G + g
        v_ref[:bp, :vals.shape[1]] = vals
        G = 128 // M
        run = v_ref[pl.ds(0, R, stride=G)]
        for g in range(1, G):
            run = run | pltpu.roll(v_ref[pl.ds(g, R, stride=G)], g * M, 1)
    else:
        # run rows r·H + h <- tile row r's lanes [h·128, h·128 + 128)
        H = M // 128
        for h in range(H):
            w = min(128, bp - h * 128)
            f_ref[pl.ds(h, bp, stride=H), :w] = vals[:, h * 128:h * 128 + w]
        run = f_ref[:R]
    lane = jax.lax.broadcasted_iota(jnp.int32, run.shape, 1)
    k, b = 1, S + 1
    while k < R * 128:
        if k < 128:
            # moved[k'] = run[k' + k]: across lanes, then the next row
            y = pltpu.roll(run, 128 - k, 1)
            moved = jnp.where(lane < 128 - k, y, pltpu.roll(y, R - 1, 0))
        else:
            moved = pltpu.roll(run, R - k // 128, 0)
        bit = 1 << b
        run = jnp.where(
            (moved & bit) != 0, moved, jnp.where((run & bit) != 0, 0, run)
        )
        k, b = 2 * k, b + 1
    return run


def _flatten_kernel(
    tab, ids_ref, cnt_ref, oi_ref, oj_ref, v_ref, low_ref, f_ref, st_i, st_j,
    car_i, car_j, nf_ref, sem, *, bp: int,
):
    t = pl.program_id(0)
    bq, S, widths = _flatten_widths(bp)
    _, bias = _id_code(bp)
    # whole rows a step sends: at most a run's rows, and the output's
    n_bits = min(st_i.shape[1] - 1, oi_ref.shape[0]).bit_length()

    @pl.when(t == 0)
    def _():
        v_ref[...] = jnp.zeros_like(v_ref)
        f_ref[...] = jnp.zeros_like(f_ref)
        r = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 1)
        low_ref[...] = (c < r).astype(low_ref.dtype)

    i0, j0, off, n, lvl = (tab[t, c] for c in range(1, 6))
    f = off % 128  # the carried row's hits, lanes [0, f)
    slot = t % 2
    cnt = jnp.broadcast_to(cnt_ref[0], (8, bp))
    n_col = jnp.transpose(cnt)[:, :1]
    o_col = jax.lax.dot_general(
        low_ref[...], cnt.astype(low_ref.dtype), _LANES,
        precision=None if bp <= 256 else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[:, :1].astype(jnp.int32)
    for w, M in enumerate(widths):

        @pl.when(lvl == w)
        def _(M=M):
            ids = ids_ref[0, :, :min(bp, max(M, 128))].astype(jnp.int32) + bias
            lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
            r = jax.lax.broadcasted_iota(jnp.int32, (bp, 1), 0)
            vals = jnp.where(
                lane < n_col, ((r * M - o_col) << (S + 1)) | (1 << S) | ids, 0
            )
            run = _tile_run(vals, v_ref, f_ref, M, S)
            R = run.shape[0]
            pos = (
                jax.lax.broadcasted_iota(jnp.int32, run.shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, run.shape, 1)
            )
            row = (pos + (run >> (S + 1))) // M
            lane = jax.lax.broadcasted_iota(jnp.int32, run.shape, 1)
            first = jax.lax.broadcasted_iota(jnp.int32, run.shape, 0) == 0
            for ids, st, car in (
                (i0 + row, st_i, car_i), (j0 + (run & (bq - 1)), st_j, car_j)
            ):
                # the run moved right by f, behind the carried hits
                y = pltpu.roll(ids, f, 1)
                prev = jnp.where(first, car[...], pltpu.roll(y, 1, 0))
                st[slot, :R] = jnp.where(lane < f, prev, y)
                st[slot, R:R + 1] = y[R - 1:]

    full = (f + n) // 128
    car_i[...] = st_i[slot, pl.ds(full, 1)]
    car_j[...] = st_j[slot, pl.ds(full, 1)]
    def copies(s, rows, base):
        """The DMAs of ``rows`` whole staged rows of slot ``s`` to output
        row ``base``: one per set bit of ``rows``, so each has a fixed
        size and none overlaps another."""
        for b in reversed(range(n_bits)):
            a = (rows >> (b + 1)) << (b + 1)
            size = 1 << b
            yield (rows & size) != 0, [
                pltpu.make_async_copy(
                    st.at[s, pl.ds(a, size)], o.at[pl.ds(base + a, size)],
                    sem.at[s],
                )
                for st, o in ((st_i, oi_ref), (st_j, oj_ref))
            ]

    def wait(s, rows):
        for on, cps in copies(s, rows, 0):
            @pl.when(on)
            def _(cps=cps):
                for cp in cps:
                    cp.wait()

    for on, cps in copies(slot, full, off // 128):
        @pl.when(on)
        def _(cps=cps):
            for cp in cps:
                cp.start()

    @pl.when(t > 0)
    def _():
        wait(1 - slot, nf_ref[0])  # the step before's, run behind this one

    nf_ref[0] = full

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        wait(slot, full)

        @pl.when((f + n) % 128 > 0)
        def _():
            row = off // 128 + full
            for car, o in ((car_i, oi_ref), (car_j, oj_ref)):
                sync_copy(car, o.at[pl.ds(row, 1)], sem.at[slot])


def simjoin_flatten_program(table, *, bp: int, cap: int) -> CurveProgram:
    """The flatten's declaration: one step per listed table row ``(slot,
    i0, j0, off, pairs, width)`` — the id table's slot, the tile's global
    bases, its first pair's place in the block's output, its pair count
    and the index of its run width (:func:`_flatten_widths`).  Each step
    reads the slot's packed ids and row counts, lays its pairs out as one
    run in VMEM behind the row carried from the step before, and DMAs the
    whole rows to the two int32 outputs ``(cap / 128, 128)`` (i, then j),
    each row written once; the last step writes the carried row."""
    bq, _, widths = _flatten_widths(bp)
    run_rows = bq * widths[-1] // 128
    slot = lambda s, sr: (sr[s, 0], 0, 0)  # noqa: E731
    out = jax.ShapeDtypeStruct((-(-cap // 128), 128), jnp.int32)
    return CurveProgram(
        name="simjoin_flatten",
        schedule=table,
        kernel=functools.partial(_flatten_kernel, bp=bp),
        in_specs=(pl.BlockSpec((1, bp, bp), slot), pl.BlockSpec((1, 1, bp), slot)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[out, out],
        scratch_shapes=(
            pltpu.VMEM((bq, 128), jnp.int32),  # the packed tile, M <= 128
            # strictly-lower ones: the rows' exclusive cumsum on the MXU
            pltpu.VMEM((bp, bp), jnp.bfloat16 if bp <= 256 else jnp.float32),
            pltpu.VMEM((run_rows, 128), jnp.int32),  # a run, M > 128
            pltpu.VMEM((2, run_rows + 1, 128), jnp.int32),  # staged i, two slots
            pltpu.VMEM((2, run_rows + 1, 128), jnp.int32),  # staged j
            pltpu.VMEM((1, 128), jnp.int32),  # carried row, i
            pltpu.VMEM((1, 128), jnp.int32),  # carried row, j
            pltpu.SMEM((1,), jnp.int32),  # whole rows the step before sent
            pltpu.SemaphoreType.DMA((2,)),
        ),
        columns=("slot", "i0", "j0", "off", "pairs", "width"),
    )


def _flatten_tiles(ids, counts, rows, tiles, *, bp: int, cap: int, interpret):
    """The first ``cap`` hits of the packed rows of table rows ``rows``
    (-1: padding, after the live rows) as global ids ``(i, j)``, two
    int32[cap], in listed-row then row-major order: the tiles' pair
    offsets and run widths here, the pairs by :func:`simjoin_flatten_program`,
    whose grid runs the live rows only."""
    _, _, widths = _flatten_widths(bp)
    live = rows >= 0
    rows = jnp.where(live, rows, 0)
    cnt = counts.reshape(-1, bp)[rows]
    tot = jnp.where(live, jnp.sum(cnt, axis=1), 0)
    width = jnp.sum(
        jnp.max(cnt, axis=1, keepdims=True) > jnp.asarray(widths[:-1]), axis=1
    )
    table = jnp.stack([
        rows, tiles[:, 0] * bp, tiles[:, 1] * bp, jnp.cumsum(tot) - tot, tot,
        width,
    ], axis=1).astype(jnp.int32)
    program = dataclasses.replace(
        simjoin_flatten_program(table, bp=bp, cap=cap),
        grid=(jnp.sum(live, dtype=jnp.int32),),
    )
    i, j = launch(program, ids, counts, interpret=interpret)
    return i.reshape(-1)[:cap], j.reshape(-1)[:cap]


@functools.partial(
    jax.jit, static_argnames=("bp", "cap", "interpret"),
    donate_argnames=("out",),
)
def simjoin_compact(out, ids, counts, rows, tiles, perm, start, *, bp: int,
                    cap: int, interpret: bool = False):
    """``out`` (int32[M, 2]) with rows ``[start, start + cap)`` replaced
    by the first ``cap`` hits of the packed rows (:func:`_flatten_tiles`)
    as pairs ``i > j``, their ids mapped through ``perm`` (the points'
    order; None: the ids as they are).  Hits past the rows' own count
    are junk that a later block, or the caller's final slice, drops."""
    i, j = _flatten_tiles(
        ids, counts, rows, tiles, bp=bp, cap=cap, interpret=interpret
    )
    if perm is not None:
        # two 1-D gathers: the map back takes no (P, 2) index
        i, j = perm[i], perm[j]
        i, j = jnp.maximum(i, j), jnp.minimum(i, j)
    return jax.lax.dynamic_update_slice(out, jnp.stack([i, j], axis=1), (start, 0))


def _compact_block(out, ids, counts, rows, tiles, *, P: int, cap: int, bp: int,
                   start: int = 0, perm=None, interpret=False) -> jax.Array:
    """:func:`simjoin_compact` of ``P`` pairs in the listed rows, with the
    rows padded to a power of two and the compaction's counters."""
    rows = np.asarray(rows, dtype=np.int32)
    n = _bucket(len(rows))
    with span("simjoin.compact"):
        count("simjoin.pairs_out", P)
        count("simjoin.mask_rows_scanned", n)
        count("simjoin.mask_cells_scanned", n * bp * bp)
        count("simjoin.rows_flattened", n * bp)
        # padding rows (-1) run no step
        rows_p = np.full(n, -1, np.int32)
        rows_p[: len(rows)] = rows
        tiles_p = np.zeros((n, 2), np.int32)
        tiles_p[: len(rows)] = tiles
        return simjoin_compact(
            out, ids, counts, jnp.asarray(rows_p), jnp.asarray(tiles_p), perm,
            start, bp=bp, cap=cap, interpret=interpret,
        )


def pairs_from_masks(ids, counts, rows, tiles, P: int, bp: int,
                     perm=None, interpret=None) -> jax.Array:
    """int32[P, 2] pairs of pass 2's hit masks in packed form (the
    ``ids``, ``counts`` of :func:`simjoin_emit_swizzled`) at table rows
    ``rows``, in row order then row-major in-tile order.  ``tiles``
    int[len(rows), 2] holds the global (i_tile, j_tile) of each listed
    row; ``P`` is the pass-1 total, so the compaction has its exact
    size.  With ``perm`` the ids are mapped through it (the points'
    order), as :func:`simjoin_compact` does."""
    if P == 0:
        return jnp.zeros((0, 2), jnp.int32)
    return _compact_block(
        jnp.zeros((P, 2), jnp.int32), ids, counts, rows, tiles, P=P, cap=P,
        bp=bp, perm=perm, interpret=resolve_interpret(interpret),
    )


def emission_table(tiles, live) -> np.ndarray:
    """Pass-2 table: the rows of ``tiles`` (int[n, C]) plus a ``live``
    column, padded with dead rows to a power of two so few row counts
    compile."""
    tiles = np.asarray(tiles, dtype=np.int32).reshape(len(live), -1)
    n = _bucket(max(len(tiles), 1))
    out = np.zeros((n, tiles.shape[1] + 1), np.int32)
    out[: len(tiles), :-1] = tiles
    out[: len(tiles), -1] = np.asarray(live, dtype=np.int32)
    return out


def simjoin_pairs_scheduled(
    schedule,
    xp: jax.Array,
    *,
    eps: float,
    bp: int,
    n_valid: int | None = None,
    interpret: bool = False,
    steps: int | None = None,
    perm: jax.Array | None = None,
) -> jax.Array:
    """Two-pass pair emission over an ARBITRARY lower-triangle tile-pair
    schedule: int32[P, 2] pairs, i > j, in schedule-then-row-major order.

    ``schedule`` is any int32[steps, 2] set of distinct (i_tile >=
    j_tile) pairs — the pruned schedule the one-shot join builds on the
    device (ops.py: ``steps`` live rows, then padding rows), or the
    halo-pruned cohort×resident restriction the streaming service
    builds on the host each tick (serve/apps.py).  This function owns the
    host step BETWEEN the two kernel dispatches (pass-1 totals → the
    non-empty tiles and the exact pair count), so the batch and
    streaming joins cannot diverge on it.  ``xp``: (Np, D) with Np % bp
    == 0 (callers pad; ``n_valid`` is the true row count when padding
    exists).  With ``perm`` the pairs' ids are mapped through it (the
    points' order) and re-canonicalised.

    Both passes run in fixed blocks, so every compiled shape is a power
    of two of the schedule's or the data's counts: pass 1 in dispatches
    of the same ``min(PASS1_STEPS, bucket(rows of schedule))`` steps,
    each running its live steps only; pass 2 in dispatches of
    ``min(PASS2_CELLS / bp², bucket(live tiles))`` table rows, each
    flattened into its own rows of the one output, with the host at most
    one block ahead of the device.
    """
    if steps is None:
        schedule = np.asarray(schedule, dtype=np.int32)
        steps = len(schedule)
    if steps == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    block = min(PASS1_STEPS, _bucket(schedule.shape[0]))
    n_blocks = -(-steps // block)
    if schedule.shape[0] < n_blocks * block:  # a host schedule
        schedule = np.pad(schedule, ((0, n_blocks * block - schedule.shape[0]), (0, 0)))
    sched = jnp.asarray(schedule)
    count("simjoin.tile_pairs", steps)
    count("simjoin.pass1_steps", steps)
    count("simjoin.pass1_blocks", n_blocks)
    tots = []
    for b in range(n_blocks):
        with span("simjoin.pass1", block=b):
            tots.append(simjoin_totals(
                sched, b * block, min(block, steps - b * block), xp,
                steps=block, eps=float(eps), bp=bp, n_valid=n_valid,
                interpret=interpret,
            ))
    with span("simjoin.sync"):
        tots, tri = jax.device_get((tots, sched))
    tot = np.concatenate(tots)[:steps].astype(np.int64)
    P = int(tot.sum())
    if P == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    check_pair_offsets(P, bp)
    with span("simjoin.table"):
        live = np.flatnonzero(tot)
        rows = min(max(PASS2_CELLS // (bp * bp), 1), _bucket(len(live)))
        firsts = np.arange(0, len(live), rows)
        p_blk = np.add.reduceat(tot[live], firsts)
        off = np.cumsum(p_blk) - p_blk
        cap = min(P, _bucket(int(p_blk.max())))
        # bucket(live) rows: a whole number of blocks
        table = emission_table(tri[live], np.ones(len(live)))
    count("simjoin.tiles_live", len(live))
    count("simjoin.pass2_blocks", len(firsts))
    out = jnp.zeros((P + cap, 2), jnp.int32)
    counts = None
    for b, first in enumerate(firsts):
        blk = table[first : first + rows]
        with span("simjoin.pass2", block=b):
            if counts is not None:
                # at most one block ahead of the device: the block
                # before's emit has run (its flatten is next), so no more
                # than two blocks' packed ids are held at once
                jax.block_until_ready(counts)
            ids, counts = simjoin_emit_swizzled(
                jnp.asarray(blk), xp, eps=float(eps), bp=bp, n_valid=n_valid,
                interpret=interpret,
            )
        out = _compact_block(
            out, ids, counts, np.arange(rows), blk[:, :2], P=int(p_blk[b]),
            cap=cap, bp=bp, start=int(off[b]), perm=perm, interpret=interpret,
        )
    return out[:P]
