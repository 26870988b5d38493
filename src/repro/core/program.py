"""CurveProgram — the declarative contract of a curve-scheduled kernel.

PRs 3-4 grew five phase-fused applications (matmul, Floyd-Warshall,
Cholesky, Lloyd k-means, ε-join) that all share one dispatch shape: a
scalar-prefetched schedule table drives the ``index_map`` of every
operand, the kernel predicates on a prefetched phase id (``pl.when``),
RMW state lives in output refs or VMEM scratch, and a retained
multi-dispatch reference provides the bit-exact oracle.  The machinery
around that shape — grid-spec assembly, the interpret/TPU switch, the
dispatch spy, the VMEM residency arithmetic — was copy-pasted per
kernel.

This module extracts the *declaration* half of that subsystem:
:class:`CurveProgram` names everything a launcher needs to dispatch a
fused kernel (schedule + phase names + block/scratch specs + aliasing +
the paired reference oracle), :func:`CurveProgram.vmem_bytes` gives the
documented residency estimate that gates the fused path against a
configurable budget (:func:`set_vmem_budget` / ``REPRO_VMEM_BUDGET``),
and :func:`curve_partition` is the schedule-level primitive behind the
``shard_map`` scale-out: contiguous ranges of an already-curve-ordered
schedule are exactly the compact low-surface shards the paper's
locality argument promises (§4-5).

The *execution* half lives in :mod:`repro.kernels.launch` (kernels
import jax.experimental.pallas; core stays importable without it).
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Callable, Mapping

import numpy as np

__all__ = [
    "CurveProgram",
    "VMEM_BUDGET_DEFAULT",
    "curve_partition",
    "fits_vmem",
    "fused_fits",
    "get_vmem_budget",
    "set_vmem_budget",
]


@dataclasses.dataclass(frozen=True)
class CurveProgram:
    """Everything a fused curve-scheduled ``pallas_call`` is, minus the call.

    Fields:

    * ``schedule`` — the int32[steps, C] scalar-prefetch table (device
      array; host tables are LRU-cached upstream in
      :mod:`repro.core.schedule`).  Passed as the prefetch operand by
      the launcher; every ``index_map`` reads it.
    * ``kernel`` — the kernel body ``(sched_ref, *in_refs, *out_refs,
      *scratch_refs)``; phase predication (``pl.when`` on a prefetched
      phase column) is the kernel's business, the program only *names*
      the phases.
    * ``in_specs`` / ``out_specs`` / ``out_shape`` / ``scratch_shapes``
      — exactly the ``pallas_call`` arguments (``out_specs`` and
      ``out_shape`` may be a single spec/struct or a list).
    * ``grid`` — defaults to ``(steps,)``; multi-dim grids (e.g. the
      2-D-schedule matmul's ``(steps, k_tiles)``) override it.
    * ``input_output_aliases`` — donation map for in-place RMW kernels
      (the interpret-exact aliased-output form, DESIGN.md
      §Phase-fusion).
    * ``phases`` / ``columns`` — documentation of the schedule layout
      (phase names, column meanings); ``columns`` lets audits find the
      (i, j) projection without grepping the kernel.
    * ``reference`` — the paired bit-identical multi-dispatch oracle
      (the retained pre-fusion implementation).  The ops wrappers fall
      back to it when :func:`fits_vmem` says the fused residency
      exceeds the configured budget.
    """

    name: str
    schedule: Any
    kernel: Callable
    in_specs: tuple
    out_specs: Any
    out_shape: Any
    grid: tuple[int, ...] | None = None
    scratch_shapes: tuple = ()
    input_output_aliases: Mapping[int, int] = dataclasses.field(
        default_factory=dict
    )
    phases: tuple[str, ...] = ()
    columns: tuple[str, ...] = ()
    reference: Callable | None = None
    choice: Any = None
    schedule_args: tuple = ()

    @property
    def steps(self) -> int:
        return int(self.schedule.shape[0])

    @property
    def signature(self) -> tuple:
        """Hashable tick-shape key: ``(name, steps, grid, columns,
        choice_key)``.

        Two launches with equal signatures trace identically — the
        schedule is a *traced* operand, so only its SHAPE (plus the
        grid and the kernel identity the name stands for) keys the jit
        cache.  The streaming services (serve/apps.py) record the
        signatures they dispatch to count expected retraces per tick
        shape instead of guessing from wall time.  ``choice_key`` (the
        :meth:`repro.core.ScheduleChoice.key` string, ``None`` when no
        choice was recorded) is a conservative refinement: it splits
        same-shape launches that run different traversal orders, so the
        autotuner's per-choice accounting can key on the signature too.
        """
        grid = self.grid if self.grid is not None else (self.steps,)
        ck = self.choice.key() if self.choice is not None else None
        return (
            self.name, self.steps, tuple(int(g) for g in grid),
            self.columns, ck,
        )

    def with_schedule(
        self, schedule, *, out_specs=None, out_shape=None, choice=None
    ) -> "CurveProgram":
        """Tick-relaunch constructor AND the schedule swap point: the
        same declaration over a new schedule table.  A streaming service
        re-issues one program per tick with that tick's (usually
        differently-sized) table; the autotuner swaps in another curve's
        table for the same grid (passing ``choice=`` so the program's
        recorded :class:`repro.core.ScheduleChoice` — and therefore its
        ``signature`` — follows the table).  Kernel, block specs, phases
        and the paired reference all carry over.  ``out_specs`` /
        ``out_shape`` override the outputs when they depend on the step
        count (e.g. per-step partial-sum rows).  The column arity is
        validated so a 4-column emission table can never silently drive
        a 2-column program's index maps."""
        if self.columns and int(schedule.shape[-1]) != len(self.columns):
            raise ValueError(
                f"{self.name}: schedule has {int(schedule.shape[-1])} "
                f"columns, program declares {len(self.columns)} "
                f"({self.columns})"
            )
        kw: dict[str, Any] = {"schedule": schedule}
        if out_specs is not None:
            kw["out_specs"] = out_specs
        if out_shape is not None:
            kw["out_shape"] = out_shape
        if choice is not None:
            kw["choice"] = choice
        return dataclasses.replace(self, **kw)

    def _out_items(self):
        outs = self.out_shape
        specs = self.out_specs
        if not isinstance(outs, (list, tuple)):
            outs, specs = [outs], [specs]
        return list(zip(specs, outs))

    def vmem_bytes(self, *operands) -> int:
        """Estimated VMEM residency of one pipelined step, in bytes.

        The model: Pallas double-buffers every streamed operand/output
        block (×2 per block — one live, one in flight), scratch buffers
        are single-buffered carried state, and the scalar-prefetch
        table lives in SMEM (excluded).  Block dims declared ``None``
        take the full operand extent.  This is the number the fused ↔
        reference fallback gate compares against
        :func:`get_vmem_budget`; it is an *estimate* of the dominant
        terms, not a Mosaic allocation oracle (lane padding and
        compiler temporaries are ignored).
        """
        if len(operands) != len(self.in_specs):
            raise ValueError(
                f"{self.name}: vmem_bytes needs one operand per in_spec "
                f"({len(self.in_specs)}), got {len(operands)}"
            )
        total = 0
        items = list(zip(self.in_specs, operands)) + self._out_items()
        for spec, arr in items:
            total += 2 * _block_bytes(spec, arr)
        for sc in self.scratch_shapes:
            shape = getattr(sc, "shape", None)
            dtype = getattr(sc, "dtype", None)
            try:
                itemsize = np.dtype(dtype).itemsize
            except TypeError:  # semaphores
                continue
            if shape is None:
                continue
            total += int(np.prod(shape)) * itemsize
        return total


def _block_bytes(spec, arr) -> int:
    """Bytes of one VMEM buffer of ``spec``'s block over ``arr``.  A spec
    without a block shape covers the whole operand; if it also names a
    memory space other than VMEM (``pl.ANY`` for HBM-resident operands
    moved by explicit DMA, SMEM for scalars) it takes no VMEM."""
    block = spec.block_shape
    if block is None:
        space = getattr(spec, "memory_space", None)
        if space is not None and "VMEM" not in str(space).upper():
            return 0
        block = (None,) * len(arr.shape)
    shape = tuple(
        int(b) if b is not None else int(s) for b, s in zip(block, arr.shape)
    )
    return int(np.prod(shape)) * np.dtype(arr.dtype).itemsize


# ---------------------------------------------------------------------------
# VMEM budget: the fused ↔ retained-reference fallback gate
# ---------------------------------------------------------------------------

class _Default:
    """Sentinel: no explicit budget set — defer to the env var."""

    def __repr__(self):
        return "VMEM_BUDGET_DEFAULT"


VMEM_BUDGET_DEFAULT = _Default()
_VMEM_BUDGET: Any = VMEM_BUDGET_DEFAULT


def set_vmem_budget(nbytes) -> Any:
    """Set the VMEM residency budget (bytes) the fused kernels are gated
    against.  Tri-state: an ``int`` caps residency, ``None`` means
    *explicitly unlimited* (overrides ``REPRO_VMEM_BUDGET``), and
    :data:`VMEM_BUDGET_DEFAULT` restores the default (env var if set,
    else unlimited).  Returns the previous setting, so
    ``old = set_vmem_budget(...); ...; set_vmem_budget(old)``
    round-trips exactly."""
    global _VMEM_BUDGET
    old = _VMEM_BUDGET
    if nbytes is None or isinstance(nbytes, _Default):
        _VMEM_BUDGET = nbytes
    else:
        _VMEM_BUDGET = int(nbytes)
    return old


def get_vmem_budget() -> int | None:
    """Current VMEM budget in bytes, or ``None`` for unlimited.

    Precedence: :func:`set_vmem_budget` (int or explicit ``None``) >
    ``REPRO_VMEM_BUDGET`` env var > unlimited.  On a real 16 MiB/core
    TPU the sensible production setting is ~``12 * 2**20`` (leave
    headroom for compiler temporaries).
    """
    if not isinstance(_VMEM_BUDGET, _Default):
        return _VMEM_BUDGET
    env = os.environ.get("REPRO_VMEM_BUDGET")
    return int(env) if env else None


def fits_vmem(program: CurveProgram, *operands) -> bool:
    """True iff ``program``'s estimated residency fits the configured
    budget (always True when no budget is set).  The ops wrappers use
    this to fall back from the fused single-dispatch path to the
    program's retained ``reference`` oracle — documented in DESIGN.md
    §Execution-layer."""
    budget = get_vmem_budget()
    return budget is None or program.vmem_bytes(*operands) <= budget


def fused_fits(app: str, program: CurveProgram, *operands) -> bool:
    """:func:`fits_vmem`, loud: when the fused form is past the budget it
    warns (``RuntimeWarning``) before the caller routes ``app`` to the
    reference path, so a run never leaves the fused kernel unseen."""
    if fits_vmem(program, *operands):
        return True
    warnings.warn(
        f"{app}: the fused kernel's VMEM residency "
        f"({program.vmem_bytes(*operands)} B) exceeds the budget "
        f"({get_vmem_budget()} B); running the multi-dispatch reference",
        RuntimeWarning,
        stacklevel=3,
    )
    return False


# ---------------------------------------------------------------------------
# Curve-range partitioning (the shard_map sharding key)
# ---------------------------------------------------------------------------

def curve_partition(sched, num_shards: int) -> np.ndarray:
    """Boundaries of a contiguous partition of a schedule's rows.

    Returns int64[num_shards + 1] ``bounds`` with shard ``s`` owning
    rows ``[bounds[s], bounds[s+1])``.  Because every schedule in this
    project is already emitted in curve order (Hilbert/FUR/FGF), a
    contiguous row range IS a contiguous Hilbert-index range — the
    compact, low-surface shard the paper's locality argument promises.

    This function is the *contract* of curve-range sharding.  The
    ``shard_map`` apps (kernels/sharded.py) consume it in its
    SPMD-uniform specialisation: they size every shard as the LARGEST
    range here (``np.diff(curve_partition(n, S)).max()``, i.e.
    ``ceil(n/S)``) and pad the tail with inert rows, because
    ``shard_map`` traces one program for all shards and needs equal
    shapes.

    Properties (property-tested in tests/test_apps_sharded.py): the
    ranges are pairwise disjoint, cover every row exactly once, stay
    contiguous in schedule (= curve) order, and their sizes differ by
    at most 1.
    """
    n = int(sched if np.isscalar(sched) else np.asarray(sched).shape[0])
    s = int(num_shards)
    if s <= 0:
        raise ValueError(f"num_shards must be positive, got {num_shards}")
    # balanced: the first n % s shards get one extra row
    base, extra = divmod(n, s)
    sizes = np.full(s, base, dtype=np.int64)
    sizes[:extra] += 1
    return np.concatenate([[0], np.cumsum(sizes)])
