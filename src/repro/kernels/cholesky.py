"""Blocked Cholesky with a phase-fused FGF-Hilbert schedule (paper §7).

Like Floyd-Warshall, Cholesky has data dependencies incompatible with a
free traversal; the paper decomposes the grid into maximal order-free
parts.  For the right-looking factorisation those are:

  per k-block:  (1) L_kk   = chol(A_kk)                     (diag)
                (2) L_ik   = A_ik · L_kk^-T   for i > k     (panel)
                (3) A_ij  -= L_ik · L_jk^T    for k < j <= i ← order-free

:func:`cholesky_blocked` fuses all three phases of every k-block into a
single ``pallas_call`` driven by the :func:`repro.core.phased_schedule`
table (columns ``(phase, k, i, j)``): the kernel predicates on the
prefetched phase id, factors the diagonal tile and solves the panel
tiles *in kernel* (masked fori_loop forms of the textbook algorithms —
:func:`_chol_tile`, :func:`_solve_tile`), and carries L_kk plus the
finished L_*k panel across grid steps in VMEM scratch (``b*b + b*n``
f32).  Phase (3), the O(n³) hot spot, consumes the panel in FGF-Hilbert
*triangle* order (jump-over, §6.2): only lower-triangular trailing
tiles are enumerated and one of the two L panels is VMEM-resident at
every step.  The matrix stays in HBM (``pl.ANY``, aliased in place) and
each step moves its tile with waited DMAs — tiles are revisited once
per k-block, and the TPU pipeline never re-fetches a revisited output
block (DESIGN.md §Phase-fusion).

:func:`cholesky_blocked_reference` retains the per-k host loop — one
diag + panel + trailing ``pallas_call`` per k-block — as the bit-exact
differential oracle.  Both paths run the SAME tile math on the same
values in the same order (the reference's diag/panel phases call
``_chol_tile``/``_solve_tile`` through single-purpose kernels instead of
``lax.linalg`` precisely so the fused path can be validated to the last
bit; accuracy vs. ``jnp.linalg.cholesky`` is covered by the oracle
tests in test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    CHOLESKY_PHASES,
    as_choice,
    phased_schedule,
    phased_schedule_device,
)
from repro.core.program import CurveProgram

from .launch import launch, sync_copy
from .launch import tile_ref as tile_ref_of
from .matmul import tile_update_swizzled


def _pick(v, mask, axis):
    """The one element of ``v`` on ``mask`` along ``axis`` (exact: every
    other term of the sum is zero) — dynamic indexing of a value by
    mask, which Mosaic lowers where ``dynamic_slice`` has no lowering."""
    return jnp.sum(jnp.where(mask, v, 0.0), axis=axis, keepdims=True)


def _chol_tile(a):
    """Right-looking Cholesky of one (b, b) SPD f32 tile.

    Textbook column loop with masked rank-1 trailing updates; column
    ``t`` is picked by mask, so the same code runs on host and inside
    the Pallas kernel.  Only the lower triangle is read; the upper
    triangle comes back zeroed — ``jnp.linalg.cholesky``'s layout.
    """
    b = a.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)

    def body(t, a):
        col = _pick(a, ci == t, 1)  # (b, 1)
        d = jnp.sqrt(_pick(col, ri[:, :1] == t, 0))  # (1, 1)
        below = jnp.where(ri[:, :1] > t, col / d, 0.0)
        below_row = _pick(below, ri == ci, 0)  # the same vector as a row
        a = a - below * below_row
        return jnp.where(ci == t, jnp.where(ri[:, :1] == t, d, below), a)

    return jax.lax.fori_loop(0, b, body, a)


def _solve_tile(l, a):
    """X with X · L^T = A for one (bm, b) tile (forward substitution).

    Row-wise independent, so tiling the panel over rows is exact; the
    column loop matches the dependency order L imposes.
    """
    bm, b = a.shape
    ci = jax.lax.broadcasted_iota(jnp.int32, (bm, b), 1)
    lr = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    lc = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    def body(t, x):
        lrow = _pick(l, lr == t, 0)  # (1, b)
        ltt = _pick(lrow, lc == t, 1)  # (1, 1)
        dot = jnp.sum(x * jnp.where(lc < t, lrow, 0.0), axis=1, keepdims=True)
        xt = (_pick(a, ci == t, 1) - dot) / ltt
        return jnp.where(ci == t, xt, x)

    return jax.lax.fori_loop(0, b, body, jnp.zeros_like(a))


def _diag_kernel(a_in, o_ref):
    o_ref[...] = _chol_tile(a_in[...].astype(jnp.float32)).astype(o_ref.dtype)


def _panel_kernel(diag_ref, p_in, p_out):
    p_out[...] = _solve_tile(
        diag_ref[...].astype(jnp.float32), p_in[...].astype(jnp.float32)
    ).astype(p_out.dtype)


def _fused_chol_kernel(
    sched_ref, a_in_ref, o_ref, tile_ref, diag_ref, panel_ref, sem, *, b
):
    """One phased-schedule step: branch on the prefetched phase id.

    Same RMW discipline as the fused FW kernel: the matrix stays in HBM
    and the step's tile moves by waited DMA; L_kk and the finished L_*k
    panel live in VMEM scratch between steps.
    """
    del a_in_ref  # aliased donor: o_ref is the same HBM buffer
    s = pl.program_id(0)
    phase = sched_ref[s, 0]
    i = sched_ref[s, 2]
    j = sched_ref[s, 3]
    blk = tile_ref_of(o_ref, i, j, b, b)
    sync_copy(blk, tile_ref, sem)

    @pl.when(phase == 0)
    def _diag():
        l = _chol_tile(tile_ref[...])
        tile_ref[...] = l
        diag_ref[...] = l

    @pl.when(phase == 1)
    def _panel():
        x = _solve_tile(diag_ref[...], tile_ref[...])
        tile_ref[...] = x
        panel_ref[pl.ds(pl.multiple_of(i * b, b), b), :] = x

    @pl.when(phase == 2)
    def _trailing():
        lik = panel_ref[pl.ds(pl.multiple_of(i * b, b), b), :]
        ljk = panel_ref[pl.ds(pl.multiple_of(j * b, b), b), :]
        # same expression as matmul._accum_update_kernel (alpha = -1)
        tile_ref[...] = tile_ref[...] + (-1.0) * jax.lax.dot_general(
            lik, ljk, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    sync_copy(tile_ref, blk, sem)


def cholesky_program(choice, nt: int, b: int) -> CurveProgram:
    """The fused-Cholesky declaration: L_kk plus the finished L_*k panel
    carried in VMEM scratch (``2·b·b + b·n`` f32 — the residency the
    ops wrapper gates on), the matrix in HBM moved one tile per step by
    waited DMA, trailing SYRK tiles in FGF-Hilbert triangle order.

    ``choice`` is a curve name or a ``phased:cholesky``
    :class:`repro.core.ScheduleChoice`; the normalised choice and grid
    args are recorded on the program for the ``with_schedule`` curve
    swap (see :func:`repro.kernels.floyd_warshall.fw_program`)."""
    choice = as_choice(choice, kind="phased:cholesky").with_(block=(int(b),))
    curve = choice.curve
    n = nt * b
    return CurveProgram(
        name=f"cholesky_fused_{curve}",
        schedule=phased_schedule_device(curve, nt, kind="cholesky"),
        kernel=functools.partial(_fused_chol_kernel, b=b),
        in_specs=(pl.BlockSpec(memory_space=pl.ANY),),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=(
            pltpu.VMEM((b, b), jnp.float32),   # the step's (i, j) tile
            pltpu.VMEM((b, b), jnp.float32),   # L_kk
            pltpu.VMEM((n, b), jnp.float32),   # L_*k panel (absolute tiles)
            pltpu.SemaphoreType.DMA(()),
        ),
        input_output_aliases={1: 0},
        phases=CHOLESKY_PHASES,
        columns=("phase", "k", "i", "j", "first_visit"),
        reference=lambda a, **kw: cholesky_blocked_reference(a, **kw),
        choice=choice,
        schedule_args=(nt,),
    )


@functools.partial(jax.jit, static_argnames=("b", "curve", "interpret"))
def cholesky_blocked(
    a: jax.Array, *, b: int = 128, curve: str = "hilbert", interpret: bool = False
) -> jax.Array:
    """Lower Cholesky factor; a: (n, n) SPD f32, n % b == 0.

    One :func:`repro.kernels.launch.launch` of :func:`cholesky_program`:
    grid = total phased-schedule steps across all k-blocks
    (diag/panel/trailing), in-place aliased updates.  Bit-identical
    (interpret f32) to :func:`cholesky_blocked_reference`.
    """
    n = a.shape[0]
    assert a.shape == (n, n) and n % b == 0
    out = launch(
        cholesky_program(curve, n // b, b), a.astype(jnp.float32),
        interpret=interpret,
    )
    return jnp.tril(out)


@functools.partial(jax.jit, static_argnames=("b", "curve", "interpret"))
def cholesky_blocked_reference(
    a: jax.Array, *, b: int = 128, curve: str = "hilbert", interpret: bool = False
) -> jax.Array:
    """Per-k-block oracle: diag + panel + trailing ``pallas_call`` per k.

    The pre-fusion host-loop implementation, retained as the bit-exact
    differential oracle (and dispatch-count baseline) for
    :func:`cholesky_blocked`.
    """
    n = a.shape[0]
    assert a.shape == (n, n) and n % b == 0
    nt = n // b
    a = a.astype(jnp.float32)
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    for kb in range(nt):
        spec_kk = pl.BlockSpec((b, b), lambda *_: (kb, kb))  # noqa: B023

        # (1) diagonal factor (in place)
        a = pl.pallas_call(
            _diag_kernel,
            grid=(1,),
            in_specs=[spec_kk],
            out_specs=spec_kk,
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={0: 0},
            compiler_params=params,
            interpret=interpret,
        )(a)

        rem = nt - kb - 1
        if rem == 0:
            continue

        lkk = jax.lax.dynamic_slice(a, (kb * b, kb * b), (b, b))

        # (2) panel solve: L_ik = A_ik · L_kk^-T, one tile per grid step
        a = pl.pallas_call(
            _panel_kernel,
            grid=(rem,),
            in_specs=[
                pl.BlockSpec((b, b), lambda t: (0, 0)),
                pl.BlockSpec((b, b), lambda t: (kb + 1 + t, kb)),  # noqa: B023
            ],
            out_specs=pl.BlockSpec((b, b), lambda t: (kb + 1 + t, kb)),  # noqa: B023
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={1: 0},
            compiler_params=params,
            interpret=interpret,
        )(lkk, a)

        # (3) trailing SYRK over lower-triangle tiles, FGF-Hilbert order.
        # Panel array indexed by ABSOLUTE tile ids (rows < (kb+1)b unused);
        # the trailing rows of the phased table are exactly this sub-grid's
        # triangle_schedule offset by kb+1.
        lik = jax.lax.dynamic_slice(a, ((kb + 1) * b, kb * b), (rem * b, b))
        panel = jnp.zeros((n, b), dtype=jnp.float32)
        panel = jax.lax.dynamic_update_slice(panel, lik, ((kb + 1) * b, 0))
        table = phased_schedule(curve, nt, kind="cholesky")
        sched = table[(table[:, 0] == 2) & (table[:, 1] == kb)][:, 2:4]
        a = tile_update_swizzled(
            jnp.asarray(sched, dtype=jnp.int32), a, panel, panel,
            bm=b, bn=b, alpha=-1.0, interpret=interpret,
        )

    return jnp.tril(a)
