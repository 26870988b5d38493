"""launch() — the single ``pallas_call`` builder for every CurveProgram.

Before this layer, each of the five fused §7 applications carried its
own copy of the dispatch machinery: a ``PrefetchScalarGridSpec`` with
the schedule as operand 0, ``dimension_semantics=("arbitrary", ...)``,
the interpret/TPU switch, input/output aliasing for the in-place RMW
kernels, and the pallas-call spy the single-dispatch tests count.
:func:`launch` is that machinery, once: it takes a
:class:`repro.core.CurveProgram` declaration plus the operands and
issues exactly one ``pallas_call``.

Execution contract (what a TPU v5e showed, DESIGN.md §Execution-layer):
a pipelined output block is written back to HBM when its block index
changes and is **never re-fetched** — a later, non-consecutive revisit
of the same block sees whatever the VMEM buffer last held, not the HBM
contents.  Only consecutive steps on one block index may accumulate in
place.  So every kernel that revisits a tile out of order keeps that
tile in ``memory_space=pl.ANY`` (HBM) and moves it with explicit,
waited DMAs (fused FW/Cholesky, 3-D matmul), or keeps the running
state in VMEM scratch (fused Lloyd).  Interpret mode happens to
re-fetch revisited output blocks, so it cannot tell the two apart;
only the chip can.

The schedule table is scalar-prefetched into SMEM flattened to one
dimension: a 2-D int32 table is padded to 128 words per row there, so
a (steps, 4) table would cost 32x its size.  Kernels and index maps
still read ``sched[s, c]`` through :class:`FlatTable`.

The dispatch spy (:class:`PallasCallCounter`) is re-exported here as
part of the execution layer's public surface; it keeps working because
``launch`` resolves ``pl.pallas_call`` late (attribute access at call
time), exactly like the pre-refactor kernels did.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.program import CurveProgram

from .pallas_compat import PallasCallCounter

__all__ = [
    "FlatTable",
    "PallasCallCounter",
    "collective_volume",
    "count_collectives",
    "flat_kernel",
    "flat_table_spec",
    "launch",
    "on_tpu",
    "resolve_interpret",
    "sync_copy",
    "tile_ref",
    "vmem_limit",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(flag):
    """The interpret/TPU switch: ``None`` means "interpret unless the
    default backend is a real TPU"; an explicit bool is passed through,
    and so is a ``pltpu.InterpretParams`` — the TPU-semantics
    interpreter, which (unlike ``interpret=True``) refuses a revisited
    output block the way the chip's pipeline would mishandle it."""
    return not on_tpu() if flag is None else flag


# VMEM a kernel may claim without asking; past it the limit is raised
# to the program's own estimate plus headroom (v5e has 128 MiB per core)
_DEFAULT_SCOPED_VMEM = 16 * 2**20
_MAX_SCOPED_VMEM = 100 * 2**20


def vmem_limit(need: int) -> int | None:
    """Scoped-VMEM limit for a kernel whose buffers need ``need`` bytes:
    ``None`` (the compiler default) while that leaves room for compiler
    temporaries, else the need plus headroom, capped below the chip's
    VMEM."""
    if need <= _DEFAULT_SCOPED_VMEM * 3 // 4:
        return None
    return min(need + need // 2 + 4 * 2**20, _MAX_SCOPED_VMEM)


class FlatTable:
    """2-D ``table[s, c]`` view of a schedule table that was flattened
    to 1-D for SMEM (``cols`` columns per row)."""

    def __init__(self, ref, cols: int):
        self.ref = ref
        self.cols = cols

    def __getitem__(self, idx):
        s, c = idx
        return self.ref[s * self.cols + c]


def flat_table_spec(spec, cols: int, n_grid: int):
    """``spec`` with its index map reading the first prefetch operand
    (the schedule, right after the ``n_grid`` grid indices) through
    :class:`FlatTable`."""
    if spec.index_map is None:
        return spec
    fn = spec.index_map

    def index_map(*args):
        args = list(args)
        args[n_grid] = FlatTable(args[n_grid], cols)
        return fn(*args)

    return dataclasses.replace(spec, index_map=index_map)


def flat_kernel(kernel, cols: int):
    """``kernel`` with its first ref, the flattened schedule, read
    through :class:`FlatTable`."""

    def run(sched_ref, *refs):
        return kernel(FlatTable(sched_ref, cols), *refs)

    return run


def sync_copy(src, dst, sem) -> None:
    """One DMA, started and waited: the RMW kernels' tile moves between
    an HBM (``pl.ANY``) operand and VMEM scratch.  Waiting before the
    step ends is what makes a later revisit read the written bytes."""
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def tile_ref(ref, i, j, bm: int, bn: int):
    """The (bm, bn) tile (i, j) of a 2-D HBM ref, for :func:`sync_copy`."""
    return ref.at[
        pl.ds(pl.multiple_of(i * bm, bm), bm),
        pl.ds(pl.multiple_of(j * bn, bn), bn),
    ]


def launch(
    program: CurveProgram, *operands,
    interpret: bool | None = None, choice=None,
):
    """Dispatch ``program`` over ``operands`` as ONE ``pallas_call``.

    Builds the scalar-prefetch grid spec from the declaration (grid
    defaults to one step per schedule row), marks every grid dimension
    ``arbitrary`` (schedule order is data, not structure — XLA must not
    reorder it), applies the program's donation map, and prepends the
    schedule as the prefetch operand.

    ``choice`` makes the traversal order tunable at the dispatch site:
    ``None`` (default) launches the program exactly as built;
    ``"auto"`` consults the persisted tuning cache
    (:mod:`repro.kernels.autotune`) for this app/shape-bucket/backend
    and swaps the winning curve in through the program's
    ``with_schedule`` swap point — with the cache empty or disabled the
    dispatch is bit-identical to the default; an explicit
    :class:`repro.core.ScheduleChoice` (or curve name) swaps strictly.
    Launch never measures — measurement is :func:`autotune_app`'s job.
    """
    if choice is not None:
        from .autotune import resolve_program_choice

        program = resolve_program_choice(program, choice, operands)
    grid = program.grid if program.grid is not None else (program.steps,)
    cols = int(program.schedule.shape[1])
    flat = lambda spec: flat_table_spec(spec, cols, len(grid))  # noqa: E731
    out_specs = program.out_specs
    out_specs = (
        [flat(o) for o in out_specs] if isinstance(out_specs, (list, tuple))
        else flat(out_specs)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[flat(spec) for spec in program.in_specs],
        out_specs=out_specs,
        scratch_shapes=list(program.scratch_shapes),
    )
    call = pl.pallas_call(
        flat_kernel(program.kernel, cols),
        grid_spec=grid_spec,
        out_shape=program.out_shape,
        input_output_aliases=dict(program.input_output_aliases),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=vmem_limit(program.vmem_bytes(*operands)),
        ),
        interpret=resolve_interpret(interpret),
        name=program.name,
    )
    return call(program.schedule.reshape(-1), *operands)


# ---------------------------------------------------------------------------
# Collective accounting (sharded-app benchmark rows)
# ---------------------------------------------------------------------------

_COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "all_gather",
        "all_to_all",
        "ppermute",
        "pmax",
        "pmin",
        "reduce_scatter",
    }
)


def _sub_jaxprs(value):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def count_collectives(fn, *args, **kwargs) -> dict[str, int]:
    """Collective-primitive counts in ``fn``'s jaxpr (traced, not run).

    Recurses through every sub-jaxpr (pjit bodies, ``shard_map``,
    ``scan`` — so a psum inside a scanned Lloyd step counts once: it is
    one collective per iteration).  Used by ``bench_apps`` to record the
    communication structure of the sharded apps next to their wall
    clock.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _COLLECTIVE_PRIMS:
                counts[name] = counts.get(name, 0) + 1
            for param in eqn.params.values():
                for sub in _sub_jaxprs(param):
                    walk(sub)

    walk(closed.jaxpr)
    return counts


def _aval_bytes(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * dtype.itemsize


def _eqn_bytes(eqn) -> int:
    """Per-shard traffic model of one collective equation, from its
    per-shard avals (inside ``shard_map`` the avals ARE shard-local):

    * ``ppermute``/``all_to_all``: each shard sends/receives its operand
      once — operand bytes;
    * ``all_gather``: each shard receives everyone else's part — output
      minus operand bytes;
    * ``psum``/``pmax``/``pmin``: ring all-reduce — ~2× operand bytes
      (reduce-scatter + all-gather phases);
    * ``reduce_scatter``: operand minus output bytes.
    """
    name = eqn.primitive.name
    in_b = sum(_aval_bytes(v) for v in eqn.invars)
    out_b = sum(_aval_bytes(v) for v in eqn.outvars)
    if name == "all_gather":
        return max(out_b - in_b, 0)
    if name == "reduce_scatter":
        return max(in_b - out_b, 0)
    if name in ("psum", "pmax", "pmin"):
        return 2 * in_b
    return in_b  # ppermute, all_to_all


def collective_volume(
    fn, *args, replicated_bytes: int = 0, **kwargs
) -> dict:
    """Collective *volume* accountant: executed primitive counts plus a
    bytes-per-shard model, from ``fn``'s jaxpr (traced, not run).

    Unlike :func:`count_collectives` (static per-program counts, the
    contract of the structure tests), this walks with an execution
    multiplier — a collective inside a ``scan`` of length L counts L
    times — and prices each equation from its per-shard avals
    (:func:`_eqn_bytes`).  ``replicated_bytes`` adds caller-declared
    operand replication (a ``P(None, None)`` in_spec moves bytes per
    shard without any collective in the jaxpr — the replicated ε-join's
    entire cost).  Returns ``{"counts", "bytes", "replicated_bytes",
    "bytes_per_shard"}`` with ``bytes_per_shard`` the grand total the
    ``bench_apps``/``bench_mesh`` rows record and CI gates on.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: dict[str, int] = {}
    bts: dict[str, int] = {}

    def walk(jaxpr, mult):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            inner = mult
            if name == "scan":
                inner = mult * int(eqn.params.get("length", 1))
            if name in _COLLECTIVE_PRIMS:
                counts[name] = counts.get(name, 0) + mult
                bts[name] = bts.get(name, 0) + mult * _eqn_bytes(eqn)
            for param in eqn.params.values():
                for sub in _sub_jaxprs(param):
                    walk(sub, inner)

    walk(closed.jaxpr, 1)
    total = sum(bts.values()) + int(replicated_bytes)
    return {
        "counts": counts,
        "bytes": bts,
        "replicated_bytes": int(replicated_bytes),
        "bytes_per_shard": total,
    }
