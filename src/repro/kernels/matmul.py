"""Hilbert-swizzled blocked matmul — the paper's flagship application (§1, §7).

TPU adaptation of the cache-oblivious matrix multiplication: the Pallas
grid is linearised to ``(schedule_step, k_tile)`` and a *scalar-prefetch*
schedule table (the nano-program analogue, paper §6.3) tells ``index_map``
which (i, j) output tile each step works on.  Pallas re-copies an operand
block HBM→VMEM only when its block index changes between consecutive grid
steps, so the Hilbert/FUR property — exactly one of (i, j) changes per
step — guarantees one of the two operand panels is reused at every step,
at *any* VMEM size (cache-oblivious: the same schedule is optimal-order
for v4/v5e/v5p VMEM budgets alike).

The MXU wants 128-aligned tiles: block defaults are (bm, bn, bk) =
(256, 256, 256) with an f32 VMEM accumulator; `k` is the inner grid dim so
the accumulator lives across the K reduction and the output tile is
written exactly once (no HBM read-modify-write of C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.program import CurveProgram

from .launch import launch, sync_copy, tile_ref


def _matmul_kernel(sched_ref, a_ref, b_ref, o_ref, acc_ref, *, k_tiles: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def matmul_swizzled(
    schedule: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B over the (i, j) tile order given by ``schedule``.

    schedule: int32[(M/bm)*(N/bn), 2] — any bijective tile order (row,
    zorder, hilbert, fur...).  A: (M, K), B: (K, N); M % bm == N % bn ==
    K % bk == 0 (the public wrapper in ops.py pads).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    mt, nt, kt = M // bm, N // bn, K // bk
    assert schedule.shape == (mt * nt, 2), (schedule.shape, mt, nt)
    out_dtype = out_dtype or a.dtype

    program = CurveProgram(
        name="matmul2d",
        schedule=schedule,
        kernel=functools.partial(_matmul_kernel, k_tiles=kt),
        grid=(mt * nt, kt),
        in_specs=(
            pl.BlockSpec((bm, bk), lambda s, k, sr: (sr[s, 0], k)),
            pl.BlockSpec((bk, bn), lambda s, k, sr: (k, sr[s, 1])),
        ),
        out_specs=pl.BlockSpec((bm, bn), lambda s, k, sr: (sr[s, 0], sr[s, 1])),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=(pltpu.VMEM((bm, bn), jnp.float32),),
        columns=("i", "j"),
    )
    return launch(program, a, b, interpret=interpret)


def _matmul3d_kernel(sched_ref, a_ref, b_ref, o_ref, acc_ref, sem, *,
                     steps: int, bm: int, bn: int):
    """One (i, j, k) tile product accumulated into C(i, j).

    C stays in HBM (``pl.ANY``): the f32 accumulator tile is loaded by
    waited DMA when the step starts a run on a revisited (i, j) and
    stored when the run ends, so a later revisit reads the stored
    partial sum.  Consecutive steps on one (i, j) accumulate in VMEM.
    """
    s = pl.program_id(0)
    i = sched_ref[s, 0]
    j = sched_ref[s, 1]
    prv = jnp.maximum(s - 1, 0)
    nxt = jnp.minimum(s + 1, steps - 1)
    run_starts = (s == 0) | (sched_ref[prv, 0] != i) | (sched_ref[prv, 1] != j)
    run_ends = (
        (s == steps - 1) | (sched_ref[nxt, 0] != i) | (sched_ref[nxt, 1] != j)
    )
    first = sched_ref[s, 3] == 1  # first visit of this (i, j) output tile
    blk = tile_ref(o_ref, i, j, bm, bn)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(run_starts & jnp.logical_not(first))
    def _load():
        sync_copy(blk, acc_ref, sem)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(run_ends)
    def _store():
        sync_copy(acc_ref, blk, sem)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def matmul_swizzled_3d(
    schedule: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B over a 3-D (i, j, k) tile order given by ``schedule``.

    schedule: int32[(M/bm)*(N/bn)*(K/bk), 4] — any bijective order of the
    3-D tile grid plus a first-visit flag column for the (i, j) output
    projection (``mark_first_visits(tile_schedule_nd(curve, (mt, nt,
    kt)), (0, 1))``; ops.py builds and caches this).  Unlike
    :func:`matmul_swizzled` (2-D schedule, k innermost, VMEM accumulator
    across the K reduction), every grid step here is one (i, j, k) tile
    product accumulated straight into the f32 output block — the official
    Pallas accumulation idiom, except "first visit" comes from the
    schedule table because under a 3-D curve the k digits of one output
    tile are not contiguous in the grid.

    Revisit-safety: the TPU pipeline never re-fetches a revisited
    output block, so C is HBM-resident (``pl.ANY``) and moved by waited
    DMAs — loaded when a run of steps on one (i, j) starts on a tile
    already visited, stored when the run ends (:func:`_matmul3d_kernel`).
    Every store completes before the step ends, so any revisit gap is
    exact, on the chip and in interpret mode alike.

    The payoff (paper §1, generalised): a unit-step 3-D schedule
    changes one of (i, j, k) per step, so of the tiles A(i,k) / B(k,j) /
    C(i,j) exactly one is guaranteed resident at every step at *any*
    VMEM size, and — unlike row-major, whose k-innermost sweep never
    revisits within reach — the Hilbert order keeps revisits clustered,
    so any tile cache beyond one block (multi-buffered VMEM, HBM
    locality) hits where row-major misses (2-3x fewer tile moves at
    realistic cache sizes; bench_locality run_3d).  The 2-D path stays
    the default in ops.py (its output tiles are written exactly once
    and it needs no f32 HBM round-trips).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    mt, nt, kt = M // bm, N // bn, K // bk
    assert schedule.shape == (mt * nt * kt, 4), (schedule.shape, mt, nt, kt)
    out_dtype = out_dtype or a.dtype

    program = CurveProgram(
        name="matmul3d",
        schedule=schedule,
        kernel=functools.partial(
            _matmul3d_kernel, steps=mt * nt * kt, bm=bm, bn=bn
        ),
        in_specs=(
            pl.BlockSpec((bm, bk), lambda s, sr: (sr[s, 0], sr[s, 2])),
            pl.BlockSpec((bk, bn), lambda s, sr: (sr[s, 2], sr[s, 1])),
        ),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=(
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ),
        columns=("i", "j", "k", "first_visit"),
        reference=matmul_swizzled,
    )
    out = launch(program, a, b, interpret=interpret)
    return out.astype(out_dtype)


def _accum_update_kernel(sched_ref, o_in_ref, a_ref, b_ref, o_ref, *, alpha: float):
    """o += alpha * (a @ b^T) — single-shot tile update (SYRK/GEMM trailing
    updates for Cholesky; o is input/output-aliased, each tile visited
    exactly once so the read-modify-write is hazard-free)."""
    o_ref[...] = o_in_ref[...] + alpha * jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "alpha", "interpret")
)
def tile_update_swizzled(
    schedule: jax.Array,
    o: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int,
    bn: int,
    alpha: float = -1.0,
    interpret: bool = False,
) -> jax.Array:
    """O[i,j] += alpha * A[i] @ B[j]^T for (i, j) in schedule order.

    A: (M, Kp) row panels, B: (N, Kp) row panels, O: (M, N); the schedule
    may cover any subset of tiles (e.g. the FGF lower triangle for the
    Cholesky trailing update, paper §7).  O is donated (aliased).
    """
    M, Kp = a.shape
    N, Kp2 = b.shape
    assert Kp == Kp2 and o.shape == (M, N)
    assert M % bm == 0 and N % bn == 0

    program = CurveProgram(
        name="tile_update",
        schedule=schedule,
        kernel=functools.partial(_accum_update_kernel, alpha=alpha),
        in_specs=(
            pl.BlockSpec((bm, bn), lambda s, sr: (sr[s, 0], sr[s, 1])),
            pl.BlockSpec((bm, Kp), lambda s, sr: (sr[s, 0], 0)),
            pl.BlockSpec((bn, Kp), lambda s, sr: (sr[s, 1], 0)),
        ),
        out_specs=pl.BlockSpec((bm, bn), lambda s, sr: (sr[s, 0], sr[s, 1])),
        out_shape=jax.ShapeDtypeStruct((M, N), o.dtype),
        input_output_aliases={1: 0},  # o (arg after schedule) -> output 0
        columns=("i", "j"),
    )
    return launch(program, o, a, b, interpret=interpret)
