"""Blocked Floyd-Warshall with a phase-fused Hilbert schedule (paper §7).

FW has a data dependency the Hilbert traversal must respect: iteration k
requires row k and column k to be final before the rest of the grid
updates.  The paper's prescription — "the grid was decomposed into maximum
parts which are compatible with an arbitrary traversal" — is exactly the
classic 3-phase blocked FW:

  per k-block:  (1) closure of the diagonal tile  D_kk
                (2) row panel D_k* and column panel D_*k  (min-plus with
                    the closed diagonal; embarrassingly parallel)
                (3) trailing tiles D_ij (i,j ≠ k): *order-free* → this is
                    the "maximum part compatible with arbitrary traversal",
                    scheduled in Hilbert order so each step reuses one of
                    the D_ik / D_kj panels resident in VMEM.

:func:`floyd_warshall_blocked` fuses the WHOLE phase structure — all
phases of all k-blocks — into a single ``pallas_call``: the
:func:`repro.core.phased_schedule` table carries ``(phase, k, i, j)``
per grid step, the kernel predicates on the prefetched phase id
(``pl.when``), and the closed diagonal / row / column panels are carried
across steps in VMEM scratch (``b*b + 2*b*n`` f32 — the VMEM bound of
the fused form).  The matrix itself stays in HBM (``pl.ANY``, aliased
in place): every step reads its tile with a waited DMA and writes it
back the same way, because each tile is revisited once per k-block and
the TPU pipeline never re-fetches a revisited output block (see
kernels/launch.py and DESIGN.md §Phase-fusion).

:func:`floyd_warshall_blocked_reference` retains the per-k host loop
(one diag + row + col + trailing ``pallas_call`` per k-block, every
block visited once per call) as the bit-exact oracle the fused kernel is
validated against — both paths run the same tile math
(:func:`_fw_closure`, :func:`_minplus`) on the same values in the same
order, so their f32 results are identical to the last bit.

All tiles of phase (3) are visited exactly once per k
(``phased_schedule`` asserts order-freeness per phase).  Min-plus
products run on the VPU (no MXU analogue for (min,+)) as a static
unroll over the contraction index: one lane-broadcast column plus one
sublane-broadcast row per term, no dynamic slicing of values (which has
no Mosaic lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    FW_PHASES,
    as_choice,
    phased_schedule,
    phased_schedule_device,
    tile_schedule,
)
from repro.core.program import CurveProgram

from .launch import launch, sync_copy
from .launch import tile_ref as tile_ref_of

# the fused kernel's tiles are f32 (8, 128)-aligned: blocks must be
# multiples of the sublane count
_CHUNK = 8


def _minplus(a, b):
    """(min,+) product of (bm, bk) x (bk, bn): a static unroll over the
    contraction index (min is exact, so the order of terms is free)."""
    out = a[:, 0:1] + b[0:1, :]
    for t in range(1, a.shape[1]):
        out = jnp.minimum(out, a[:, t : t + 1] + b[t : t + 1, :])
    return out


def _fw_closure(d):
    """Min-plus transitive closure of one (b, b) tile (in-tile FW)."""
    for t in range(d.shape[0]):
        d = jnp.minimum(d, d[:, t : t + 1] + d[t : t + 1, :])
    return d


def _diag_kernel(d_in, d_out):
    d_out[...] = _fw_closure(d_in[...].astype(jnp.float32)).astype(d_out.dtype)


def _row_panel_kernel(diag_ref, p_in, p_out):
    p = p_in[...].astype(jnp.float32)
    p_out[...] = jnp.minimum(p, _minplus(diag_ref[...].astype(jnp.float32), p))


def _col_panel_kernel(diag_ref, p_in, p_out):
    p = p_in[...].astype(jnp.float32)
    p_out[...] = jnp.minimum(p, _minplus(p, diag_ref[...].astype(jnp.float32)))


def _trailing_kernel(sched_ref, dik_ref, dkj_ref, d_in, d_out):
    d = d_in[...].astype(jnp.float32)
    upd = _minplus(dik_ref[...].astype(jnp.float32), dkj_ref[...].astype(jnp.float32))
    d_out[...] = jnp.minimum(d, upd)


def _fused_fw_kernel(
    sched_ref, d_in_ref, o_ref, tile_ref, diag_ref, row_ref, col_ref, sem,
    *, b,
):
    """One phased-schedule step: branch on the prefetched phase id.

    The matrix is HBM-resident (``o_ref`` aliases ``d_in_ref``); the
    step's (i, j) tile is DMA'd into ``tile_ref``, updated, and DMA'd
    back before the step ends, so a later revisit reads it from HBM.
    The closed diagonal and the finished row/column panels of the
    current k-block are carried across grid steps in VMEM scratch.
    """
    del d_in_ref  # aliased donor: o_ref is the same HBM buffer
    s = pl.program_id(0)
    phase = sched_ref[s, 0]
    i = sched_ref[s, 2]
    j = sched_ref[s, 3]
    blk = tile_ref_of(o_ref, i, j, b, b)
    sync_copy(blk, tile_ref, sem)

    @pl.when(phase == 0)
    def _diag():
        closed = _fw_closure(tile_ref[...])
        tile_ref[...] = closed
        diag_ref[...] = closed

    @pl.when(phase == 1)
    def _row():
        p = tile_ref[...]
        out = jnp.minimum(p, _minplus(diag_ref[...], p))
        tile_ref[...] = out
        row_ref[:, pl.ds(pl.multiple_of(j * b, b), b)] = out

    @pl.when(phase == 2)
    def _col():
        p = tile_ref[...]
        out = jnp.minimum(p, _minplus(p, diag_ref[...]))
        tile_ref[...] = out
        col_ref[pl.ds(pl.multiple_of(i * b, b), b), :] = out

    @pl.when(phase == 3)
    def _trailing():
        dik = col_ref[pl.ds(pl.multiple_of(i * b, b), b), :]
        dkj = row_ref[:, pl.ds(pl.multiple_of(j * b, b), b)]
        tile_ref[...] = jnp.minimum(tile_ref[...], _minplus(dik, dkj))

    sync_copy(tile_ref, blk, sem)


def fw_program(choice, nt: int, b: int) -> CurveProgram:
    """The fused-FW declaration: one grid step per phased-schedule row,
    per-k state (closed diagonal + finished row/column panels) in VMEM
    scratch, all RMW through the aliased output ref.  The VMEM bound of
    the fused form — ``b·b + 2·b·n`` f32 scratch on top of the streamed
    (b, b) blocks — is what :meth:`CurveProgram.vmem_bytes` reports and
    the ops wrapper gates on.

    ``choice`` is a curve name or a ``phased:fw``
    :class:`repro.core.ScheduleChoice`; the normalised choice (block
    pinned to the actual ``b``) and the grid args are recorded on the
    program, so ``launch(choice=...)`` can rebuild the table under a
    different curve through the ``with_schedule`` swap point."""
    choice = as_choice(choice, kind="phased:fw").with_(block=(int(b),))
    curve = choice.curve
    n = nt * b
    return CurveProgram(
        name=f"fw_fused_{curve}",
        schedule=phased_schedule_device(curve, nt, kind="fw"),
        kernel=functools.partial(_fused_fw_kernel, b=b),
        in_specs=(pl.BlockSpec(memory_space=pl.ANY),),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=(
            pltpu.VMEM((b, b), jnp.float32),   # the step's (i, j) tile
            pltpu.VMEM((b, b), jnp.float32),   # closed diagonal D_kk
            pltpu.VMEM((b, n), jnp.float32),   # row panel D_k*
            pltpu.VMEM((n, b), jnp.float32),   # column panel D_*k
            pltpu.SemaphoreType.DMA(()),
        ),
        input_output_aliases={1: 0},
        phases=FW_PHASES,
        columns=("phase", "k", "i", "j", "first_visit"),
        reference=lambda d, **kw: floyd_warshall_blocked_reference(d, **kw),
        choice=choice,
        schedule_args=(nt,),
    )


@functools.partial(jax.jit, static_argnames=("b", "curve", "interpret"))
def floyd_warshall_blocked(
    d: jax.Array, *, b: int = 128, curve: str = "hilbert", interpret: bool = False
) -> jax.Array:
    """All-pairs shortest paths; d: (n, n) f32, n % b == 0, b % 8 == 0.

    One :func:`repro.kernels.launch.launch` of :func:`fw_program`:
    grid = total phased-schedule steps across all k-blocks,
    scalar-prefetched ``(phase, k, i, j)`` table, in-place aliased
    min-updates.  Bit-identical (interpret f32) to
    :func:`floyd_warshall_blocked_reference`.
    """
    n = d.shape[0]
    assert d.shape == (n, n) and n % b == 0 and b % _CHUNK == 0
    return launch(
        fw_program(curve, n // b, b), d.astype(jnp.float32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("b", "curve", "interpret"))
def floyd_warshall_blocked_reference(
    d: jax.Array, *, b: int = 128, curve: str = "hilbert", interpret: bool = False
) -> jax.Array:
    """Per-k-block oracle: 3-4 separate ``pallas_call`` programs per k.

    The pre-fusion implementation, retained as the bit-exact differential
    oracle (and the dispatch-count baseline in ``bench_apps``) for
    :func:`floyd_warshall_blocked`.
    """
    n = d.shape[0]
    assert d.shape == (n, n) and n % b == 0 and b % _CHUNK == 0
    nt = n // b
    d = d.astype(jnp.float32)

    full = tile_schedule(curve, nt, nt).astype(np.int32)
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    for kb in range(nt):
        spec_kk = pl.BlockSpec((b, b), lambda *_: (kb, kb))  # noqa: B023

        # (1) diagonal closure (in place)
        d = pl.pallas_call(
            _diag_kernel,
            grid=(1,),
            in_specs=[spec_kk],
            out_specs=spec_kk,
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={0: 0},
            compiler_params=params,
            interpret=interpret,
        )(d)

        dkk = jax.lax.dynamic_slice(d, (kb * b, kb * b), (b, b))

        # (2) row panel D_kj (all j; j == k is idempotent on a closed diag)
        d = pl.pallas_call(
            _row_panel_kernel,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((b, b), lambda j: (0, 0)),
                pl.BlockSpec((b, b), lambda j: (kb, j)),  # noqa: B023
            ],
            out_specs=pl.BlockSpec((b, b), lambda j: (kb, j)),  # noqa: B023
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={1: 0},
            compiler_params=params,
            interpret=interpret,
        )(dkk, d)

        #     column panel D_ik (all i)
        d = pl.pallas_call(
            _col_panel_kernel,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((b, b), lambda i: (0, 0)),
                pl.BlockSpec((b, b), lambda i: (i, kb)),  # noqa: B023
            ],
            out_specs=pl.BlockSpec((b, b), lambda i: (i, kb)),  # noqa: B023
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={1: 0},
            compiler_params=params,
            interpret=interpret,
        )(dkk, d)

        # (3) trailing tiles in curve order (the order-free maximum part)
        sched = full[(full[:, 0] != kb) & (full[:, 1] != kb)]
        if len(sched) == 0:
            continue
        d_col = jax.lax.dynamic_slice(d, (0, kb * b), (n, b))  # D_*k panel
        d_row = jax.lax.dynamic_slice(d, (kb * b, 0), (b, n))  # D_k* panel
        trailing = CurveProgram(
            name="fw_trailing",
            schedule=jnp.asarray(sched, dtype=jnp.int32),
            kernel=_trailing_kernel,
            in_specs=(
                pl.BlockSpec((b, b), lambda s, sr: (sr[s, 0], 0)),
                pl.BlockSpec((b, b), lambda s, sr: (0, sr[s, 1])),
                pl.BlockSpec((b, b), lambda s, sr: (sr[s, 0], sr[s, 1])),
            ),
            out_specs=pl.BlockSpec((b, b), lambda s, sr: (sr[s, 0], sr[s, 1])),
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            input_output_aliases={3: 0},
            columns=("i", "j"),
        )
        d = launch(trailing, d_col, d_row, d, interpret=interpret)
    return d
