"""Public jit'd wrappers for the Pallas kernels.

These handle what the raw kernels don't: schedule construction (curve
choice), padding to block multiples, GQA head expansion, dtype policy and
the interpret/compiled dispatch (compiled on a TPU, the Pallas
interpreter elsewhere — the CPU tests).

Two execution-layer policies live here too (DESIGN.md §Execution-layer,
§Scale-out):

* **VMEM-budget fallback** — the fused FW, Cholesky and Lloyd programs
  estimate their residency (``vmem_bytes``); when a budget is configured
  (:func:`repro.core.set_vmem_budget` / ``REPRO_VMEM_BUDGET``) and the
  fused form exceeds it, the wrapper takes the program's retained
  multi-dispatch reference path instead (correct at any size; O(nt)
  dispatches instead of 1) and says so with a :class:`RuntimeWarning`.
* **mesh= scale-out** — ``kmeans_lloyd`` and ``simjoin_pairs`` accept a
  1-D device mesh (``repro.launch.mesh.make_app_mesh``) and run the
  curve-range-sharded shard_map variants from
  :mod:`repro.kernels.sharded`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ScheduleChoice,
    fused_fits,
    get_curve,
    kmeans_schedule,
    kmeans_schedule_device,
    tile_schedule_device,
    triangle_schedule,
)
from repro.core.tracing import count, span

from . import ref
from .attention import (
    causal_schedule,
    decode_page_schedule_device,
    flash_attention_decode,
    flash_attention_prefill,
    flash_attention_swizzled,
    full_schedule,
    prefill_page_schedule_device,
)
from .cholesky import cholesky_blocked, cholesky_blocked_reference, cholesky_program
from .floyd_warshall import (
    _CHUNK as _FW_CHUNK,
    floyd_warshall_blocked,
    floyd_warshall_blocked_reference,
    fw_program,
)
from .kmeans import (
    hilbert_point_order,
    hilbert_point_order_cached,
    kmeans_assign_swizzled,
    kmeans_init,
    kmeans_lloyd_fused,
    kmeans_lloyd_program,
    kmeans_lloyd_reference,
)
from .launch import resolve_interpret as _interpret
from .matmul import matmul_swizzled, matmul_swizzled_3d
from .simjoin import (
    simjoin_counts_swizzled,
    simjoin_inputs,
    simjoin_pairs_scheduled,
)

DEFAULT_CURVE = "fur"  # overlay-grid Hilbert: native n×m, unit steps


def _app_choice(choice, app: str, *arrays) -> ScheduleChoice | None:
    """Resolve a wrapper's ``choice=`` kwarg into a concrete
    :class:`repro.core.ScheduleChoice`, or ``None`` for "use the
    defaults" (the guaranteed bit-identical path).

    ``None`` → defaults.  ``"auto"`` → consult the persisted tuning
    cache for (app, shape-bucket, backend); a miss, a disabled cache or
    a wrong-kind entry all resolve to ``None``.  An explicit
    ScheduleChoice is kind-checked and returned as-is.  Block sizes in
    the returned choice override the wrapper's block kwargs *before*
    padding — that is why this resolution lives here and not in
    ``launch()``.
    """
    from .autotune import APP_KINDS, lookup

    kind = APP_KINDS[app]
    if choice is None:
        return None
    if isinstance(choice, str):
        if choice != "auto":
            raise ValueError(
                f"choice= takes None, 'auto' or a ScheduleChoice; use "
                f"curve= for a bare curve name (got {choice!r})"
            )
        found = lookup(app, tuple(tuple(a.shape) for a in arrays))
        return found if found is not None and found.kind == kind else None
    if not isinstance(choice, ScheduleChoice):
        raise TypeError(f"choice= expects a ScheduleChoice, got {choice!r}")
    if choice.kind != kind:
        raise ValueError(
            f"{app} needs a {kind!r} choice, got {choice.kind!r}"
        )
    return choice


def _pad2(x: jax.Array, r: int, c: int) -> jax.Array:
    pr = (-x.shape[0]) % r
    pc = (-x.shape[1]) % c
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def _block_and_pad(n: int, b: int, *, mult: int = 1) -> tuple[int, int]:
    """Pick a legal tile size for an n×n blocked kernel: ``(block, n_pad)``.

    Candidates are multiples of ``mult`` between roughly b/2 and
    ``min(b, n)``; a divisor of n wins outright (``n_pad == n``, no
    padding), otherwise the candidate minimising the padded size (larger
    block on ties).  This replaces the old ``b = min(b, n)`` +
    ``assert n % b == 0`` combo that turned e.g. n=100 into a confusing
    assertion failure.
    """
    b = max(min(b, n), mult)
    b -= b % mult
    lo = max(mult, b // 2 // mult * mult)
    best = None
    for bb in range(b, lo - 1, -mult):
        padded = -(-n // bb) * bb
        key = (padded, -bb)
        if best is None or key < best[:2]:
            best = (padded, -bb, bb)
    return best[2], best[0]


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    curve: str = DEFAULT_CURVE,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    out_dtype=None,
    schedule_ndim: int = 2,
    choice=None,
    interpret: bool | None = None,
) -> jax.Array:
    """C = A @ B with a curve-scheduled Pallas kernel (paper §1/§7).

    ``choice`` (``None`` | ``"auto"`` | a ``tile``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and the
    block sizes as one tunable value; ``"auto"`` consults the autotuner
    cache and falls back to the defaults on a miss (bit-identical).

    ``schedule_ndim=2`` (default fast path): the curve orders the (i, j)
    output tiles and k runs innermost with a VMEM-resident accumulator —
    each output tile is written exactly once.  ``schedule_ndim=3``: the
    curve orders the full (i, j, k) tile grid, so curve locality extends
    across the K axis too (one of A/B/C guaranteed resident per step,
    clustered revisits at every cache size); accumulation is a
    read-modify-write into an f32 buffer (see
    :func:`repro.kernels.matmul.matmul_swizzled_3d`).  Curves
    without 3-D support (``fur``, ``peano``) fall back to ``hilbert``.

    Schedule generation is off the hot path twice over: the table for a
    (curve, grid) pair is LRU-cached on host and device, and a *cold*
    non-power-of-two Hilbert grid is generated by the d-dimensional FGF
    jump-over (cost ∝ tiles emitted, not the 2^(d·L) cover volume — see
    ``core/fgf_nd.py``), which matters for the ragged tile counts real
    (M, N, K) problem shapes produce.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert schedule_ndim in (2, 3), schedule_ndim
    ch = _app_choice(choice, "matmul", a, b)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            bm, bn, bk = (tuple(ch.block) + (bn, bk))[:3]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    ap = _pad2(a, bm, bk)
    bp = _pad2(b, bk, bn)
    mt, nt = ap.shape[0] // bm, bp.shape[1] // bn
    if schedule_ndim == 3:
        if not get_curve(curve).supports(3):  # raises on unknown names
            curve = "hilbert"
        kt = ap.shape[1] // bk
        sched = tile_schedule_device(
            curve, (mt, nt, kt), first_visit_axes=(0, 1)
        )
        out = matmul_swizzled_3d(
            sched, ap, bp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            interpret=_interpret(interpret),
        )
    else:
        sched = tile_schedule_device(curve, (mt, nt))
        out = matmul_swizzled(
            sched, ap, bp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            interpret=_interpret(interpret),
        )
    return out[:M, :N]


MASK_TYPES = ("none", "causal", "padding", "padding_causal")


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask_type: str | None = None,
    kv_seqlen: jax.Array | None = None,
    q_seqlen: jax.Array | None = None,
    sm_scale: float | None = None,
    bq: int = 128,
    bkv: int = 128,
    serpentine: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention over (B, H, S, D) with FGF jump-over scheduling.

    This is the production batch surface (mirroring the cuDNN
    ``fused_attention_stablehlo`` integration shape):

    * ``mask_type`` — one of ``"none" | "causal" | "padding" |
      "padding_causal"``; overrides the legacy ``causal`` flag.  The
      padding variants require ``kv_seqlen``.
    * ``kv_seqlen`` — int32[B] per-sequence valid KV lengths (variable
      sequence lengths in one padded batch).  Dynamic: a scalar-prefetch
      operand of the kernel, so every padding pattern shares one
      compiled program.
    * ``q_seqlen`` — int32[B] valid query lengths; rows past a
      sequence's length are zeroed in the output (their softmax rows are
      fully masked and therefore undefined).

    GQA: if k/v have fewer heads they are expanded here; the *decode*
    kernel (:func:`attention_decode`) runs natively grouped — one KV
    head block serves its g query heads without expansion.  The batch
    (training/prefill) kernel keeps the expansion: its schedules swizzle
    (q_tile, kv_tile), and head-grouping there is a layout change the
    models don't need yet (training uses the XLA flash twin;
    ``cfg.use_hilbert_kernels`` opts into this kernel).
    """
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if mask_type is not None:
        if mask_type not in MASK_TYPES:
            raise ValueError(f"mask_type {mask_type!r}; one of {MASK_TYPES}")
        causal = mask_type in ("causal", "padding_causal")
        if "padding" in mask_type and kv_seqlen is None:
            raise ValueError(f"mask_type {mask_type!r} requires kv_seqlen")
    if Hkv != H:
        assert H % Hkv == 0
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(bq, S)
    bkv = min(bkv, S)
    if causal:
        assert bq == bkv, "causal schedule assumes square tiles"
    # make the smaller block divide the larger (round the larger down),
    # so the common tile lattice is max(bq, bkv) — padding to the raw lcm
    # could blow S up by an order of magnitude (e.g. lcm(100, 64) = 1600)
    if bq % bkv and bkv % bq:
        if bq > bkv:
            bq = bq // bkv * bkv
        else:
            bkv = bkv // bq * bq
    # ragged S: zero-pad to the tile lattice and mask the kv tail in the
    # kernel's softmax (padded q rows are sliced off the output)
    lcm = bq * bkv // math.gcd(bq, bkv)
    Sp = -(-S // lcm) * lcm
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    qt, kt = Sp // bq, Sp // bkv
    if causal:
        sched = causal_schedule(qt, None, serpentine=serpentine)
    else:
        sched = full_schedule(qt, kt, serpentine=serpentine)
    seq_bh = None
    if kv_seqlen is not None:
        seq_bh = jnp.repeat(jnp.asarray(kv_seqlen, dtype=jnp.int32), H)
    out = flash_attention_swizzled(
        jnp.asarray(sched, dtype=jnp.int32),
        q.reshape(B * H, Sp, D),
        k.reshape(B * H, Sp, D),
        v.reshape(B * H, Sp, D),
        causal=causal,
        sm_scale=sm_scale,
        bq=bq,
        bkv=bkv,
        kv_valid=S if Sp != S else None,
        kv_seqlen=seq_bh,
        interpret=_interpret(interpret),
    )
    out = out.reshape(B, H, Sp, D)[:, :, :S]
    if q_seqlen is not None:
        rows = jnp.arange(S, dtype=jnp.int32)[None] < jnp.asarray(
            q_seqlen, dtype=jnp.int32)[:, None]
        out = jnp.where(rows[:, None, :, None], out, 0)
    return out


def attention_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float | None = None,
    slot_order: tuple[int, ...] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One serving decode step against a PAGED KV cache (paper locality
    story applied to serving: page-id → memory layout follows the
    registry's Hilbert map, see :mod:`repro.serve.kv_pages`).

    q: (B, Hkv, g, Dk) grouped single-token queries (GQA: g = H // Hkv;
    MLA: Hkv=1, g=H over the latent ⊕ rope width).  k_pages/v_pages:
    (P, Hkv, page_size, Dk/Dv) physical pools; ``page_table`` int32[B,
    max_pages] and ``pos`` int32[B] are dynamic operands — allocation
    churn and ragged per-slot depths never recompile.  Returns
    (B, Hkv, g, Dv).
    """
    B = q.shape[0]
    max_pages = page_table.shape[1]
    sched = decode_page_schedule_device(
        B, max_pages, tuple(slot_order) if slot_order is not None else None
    )
    return flash_attention_decode(
        sched, page_table, pos, q, k_pages, v_pages,
        sm_scale=sm_scale, interpret=_interpret(interpret),
    )


def attention_prefill(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos0,
    n_new=None,
    *,
    sm_scale: float | None = None,
    schedule: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched causal prefill against a PAGED KV cache: one dispatch
    attends a whole cohort of prompts through the page table.

    q: (B, Tq, Hkv, g, Dk) — Tq new prompt tokens per slot (token i at
    absolute position ``pos0[slot] + i``; rows past the slot's
    new-token count are padding with undefined-but-finite output).
    ``pos0`` / ``n_new`` are the cohort's host-side admission metadata
    (per-slot resume position and new-token count) from which the
    ragged page schedule is built; pass ``schedule=`` instead when
    calling from inside a trace (the engine builds it once per
    admission via :func:`prefill_page_schedule_device`).  The new K/V
    must already be scattered into the pools (split-phase; the models
    layer does the masked scatter first).  Returns (B, Tq, Hkv, g, Dv).
    """
    ps = k_pages.shape[2]
    max_pages = page_table.shape[1]
    if schedule is None:
        if n_new is None:
            raise ValueError("attention_prefill needs n_new or schedule=")
        schedule = prefill_page_schedule_device(
            tuple(int(p) for p in pos0),
            tuple(int(n) for n in n_new),
            ps,
            max_pages,
        )
    return flash_attention_prefill(
        schedule, page_table, pos0, q, k_pages, v_pages,
        sm_scale=sm_scale, interpret=_interpret(interpret),
    )


def kmeans_assign(
    x: jax.Array,
    c: jax.Array,
    *,
    curve: str = DEFAULT_CURVE,
    bp: int = 256,
    bc: int = 128,
    hilbert_order: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(squared distance to nearest centroid, assignment) per point.

    ``hilbert_order=True`` pre-sorts the points by the d-dimensional
    Hilbert key of their (quantised) features before tiling, so each
    point tile covers a compact region of feature space (paper §6.2
    application note, generalised to d dims); results are returned in the
    original point order.
    """
    N, D = x.shape
    K, _ = c.shape
    if hilbert_order:
        perm = hilbert_point_order_cached(x)
        inv = jnp.argsort(perm)
        d2, assign = kmeans_assign(
            x[perm], c, curve=curve, bp=bp, bc=bc, interpret=interpret
        )
        return d2[inv], assign[inv]
    bp, bc = min(bp, N), min(bc, K)
    xp = _pad2(x, bp, 1)
    # zero-pad the centroids and mask the pad columns in the kernel
    # (magic 1e30 coordinates squared to inf and bred NaN intermediates)
    pc = (-K) % bc
    cp = jnp.pad(c, ((0, pc), (0, 0))) if pc else c
    pt, ct = xp.shape[0] // bp, cp.shape[0] // bc
    sched = tile_schedule_device(curve, (pt, ct))
    min_m, assign = kmeans_assign_swizzled(
        sched, xp, cp, bp=bp, bc=bc, k_valid=K if pc else None,
        interpret=_interpret(interpret),
    )
    d2 = min_m + jnp.sum(xp.astype(jnp.float32) ** 2, axis=1)
    return d2[:N], assign[:N]


def kmeans_lloyd(
    x: jax.Array,
    k: int,
    *,
    iters: int = 10,
    curve: str = DEFAULT_CURVE,
    seed: int = 0,
    bp: int = 256,
    bc: int = 128,
    hilbert_order: bool = False,
    fused: bool = True,
    mesh=None,
    shard_exact: bool = True,
    shard_reduce: str | None = None,
    choice=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full Lloyd k-means: (centroids f32[k, D], assignment int32[N]).

    ``choice`` (``None`` | ``"auto"`` | a ``kmeans``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and
    ``(bp, bc)`` as one tunable value; ``"auto"`` consults the
    autotuner cache, falling back to the defaults on a miss.

    ``fused=True`` (default) runs ONE phase-fused ``pallas_call`` per
    iteration — assignment AND per-centroid sum/count accumulation off
    the :func:`repro.core.kmeans_schedule` table — with the whole
    ``iters`` loop under ``jax.lax.scan`` (the kernel traces once).
    ``fused=False`` is the retained multi-dispatch reference (one
    assignment kernel + host-side merge + per-tile update per
    iteration); the two are bit-identical.  When the fused program's
    VMEM residency (``K·D + K`` f32 resident accumulators, 8 bytes per
    point of running (min, argmin), streamed panels) exceeds the
    configured budget (:func:`repro.core.set_vmem_budget`), the wrapper
    warns and falls back to the reference path.

    ``mesh=`` (a 1-D mesh from ``repro.launch.mesh.make_app_mesh``)
    runs the curve-range-sharded shard_map variant instead: point tiles
    partitioned contiguously across devices, psum'd count accumulators,
    and — with ``shard_exact=True`` — centroid sums folded in the
    single-core accumulation order, so the result is bit-identical to
    the single-core fused kernel on any mesh size.  ``shard_reduce``
    overrides the reduction class explicitly (``"exact"`` / ``"tree"`` /
    ``"psum"`` — see :func:`repro.kernels.sharded.kmeans_lloyd_sharded`).

    ``hilbert_order=True`` sorts the points by their d-dimensional
    Hilbert key ONCE (hoisted out of the Lloyd loop — it used to be
    recomputed every iteration — and LRU-cached on the quantised grid),
    runs all iterations in sorted order, and maps the assignment back
    through the inverse permutation at the end.
    """
    ch = _app_choice(choice, "kmeans_lloyd", x)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            bp, bc = (tuple(ch.block) + (bc,))[:2]
    if mesh is not None:
        if not fused:
            raise ValueError(
                "mesh= always runs the sharded fused path; fused=False is "
                "only available single-core (drop mesh= to use the retained "
                "multi-dispatch reference)"
            )
        from .sharded import kmeans_lloyd_sharded

        return kmeans_lloyd_sharded(
            x, k, mesh=mesh, iters=iters, curve=curve, seed=seed, bp=bp,
            bc=bc, hilbert_order=hilbert_order, exact=shard_exact,
            reduce=shard_reduce, interpret=interpret,
        )
    N, D = x.shape
    c0 = kmeans_init(x, k, seed)
    inv = None
    if hilbert_order:
        perm = hilbert_point_order_cached(x)
        inv = jnp.argsort(perm)
        x = x[perm]
    bp, bc = min(bp, N), min(bc, k)
    xp = _pad2(x, bp, 1)
    n_valid = N if xp.shape[0] != N else None
    pc = (-k) % bc
    cp = jnp.pad(c0, ((0, pc), (0, 0))) if pc else c0
    pt, ct = xp.shape[0] // bp, cp.shape[0] // bc
    k_valid = k if pc else None
    kw = dict(
        iters=iters, bp=bp, bc=bc, k_valid=k_valid, n_valid=n_valid,
        interpret=_interpret(interpret),
    )
    if fused:
        # VMEM-budget gate: the fused form keeps the (Kp, D) + (1, Kp)
        # accumulators resident; past the budget, take the reference path
        sched = kmeans_schedule_device(curve, pt, ct)
        prog = kmeans_lloyd_program(
            sched, pt=pt, ct=ct, bp=bp, bc=bc, D=D,
            k_valid=k_valid, n_valid=n_valid, choice=curve,
        )
        xT_probe = jax.ShapeDtypeStruct(xp.shape[::-1], xp.dtype)
        cn_probe = jax.ShapeDtypeStruct((cp.shape[0], 1), jnp.float32)
        fused = fused_fits("kmeans_lloyd", prog, xT_probe, cp, cn_probe)
    if fused:
        c, assign = kmeans_lloyd_fused(sched, xp, cp, **kw)
    else:
        sched = tile_schedule_device(curve, (pt, ct))
        host = kmeans_schedule(curve, pt, ct)
        upd = jnp.asarray(host[host[:, 0] == 1][:, [1, 3]], dtype=jnp.int32)
        c, assign = kmeans_lloyd_reference(sched, upd, xp, cp, **kw)
    c, assign = c[:k], assign[:N]
    if inv is not None:
        assign = assign[inv]
    return c, assign


def simjoin_counts(
    x: jax.Array,
    eps: float,
    *,
    curve: str = "hilbert",
    bp: int = 256,
    hilbert_order: bool = False,
    choice=None,
    interpret: bool | None = None,
) -> jax.Array:
    """ε-join neighbour counts with FGF-Hilbert triangle scheduling.

    ``hilbert_order=True`` sorts the points by their d-dimensional
    Hilbert key first, concentrating the join's hits near the tile-grid
    diagonal (counts come back in the original point order).

    ``choice`` (``None`` | ``"auto"`` | a ``triangle``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and ``bp``
    as one tunable value (autotuner contract; defaults on a miss).
    """
    N, D = x.shape
    if N == 0:
        return jnp.zeros((0,), dtype=jnp.int32)
    ch = _app_choice(choice, "simjoin_counts", x)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            bp = ch.block[0]
    if hilbert_order:
        # the O(N log N) point permutation is LRU-cached on the quantised
        # grid, so repeated joins over one point set don't recompute it
        perm = hilbert_point_order_cached(x)
        inv = jnp.argsort(perm)
        return simjoin_counts(
            x[perm], eps, curve=curve, bp=bp, interpret=interpret
        )[inv]
    bp = min(bp, N)
    # zero-pad and mask pad rows in the kernel by index (magic 1e15
    # coordinates overflow f32 squared distances, and the pad rows
    # ε-joined *each other* at distance 0)
    pn = (-N) % bp
    xp = jnp.pad(x, ((0, pn), (0, 0))) if pn else x
    pt = xp.shape[0] // bp
    sched = jnp.asarray(triangle_schedule(curve, pt, strict=False), dtype=jnp.int32)
    counts = simjoin_counts_swizzled(
        sched, xp, eps=float(eps), bp=bp, n_valid=N if pn else None,
        interpret=_interpret(interpret),
    )
    return counts[:N]


def simjoin_pairs(
    x: jax.Array,
    eps: float,
    *,
    curve: str = "hilbert",
    bp: int = 256,
    hilbert_order: bool = False,
    mesh=None,
    choice=None,
    interpret: bool | None = None,
) -> jax.Array:
    """The ε-join's actual output: int32[P, 2] index pairs, i > j.

    ``choice`` (``None`` | ``"auto"`` | a ``triangle``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and ``bp``
    as one tunable value (autotuner contract; defaults on a miss).

    Two-pass emission over a pruned tile-pair schedule, built on the
    device (:func:`repro.kernels.simjoin.pruned_schedule`): the
    lower-triangle tile pairs whose bounding boxes lie within ε, in the
    order ``curve`` visits them.  The point order, the ``bp`` clamp, the
    padding and this schedule are decided in one place,
    :func:`repro.kernels.simjoin.simjoin_inputs`, for both the
    single-chip and the sharded join.  Pass 1 (:func:`simjoin_totals`,
    the ``simjoin_hits`` program) gives each tile's row hit sums, whose
    totals give the exact pair count and the non-empty tiles; pass 2
    (:func:`simjoin_emit_swizzled`) packs each non-empty tile's hit rows
    and counts them, and a flatten kernel of exact size turns the packed
    rows into pairs in schedule-then-row-major order.  Both run in fixed
    blocks, so one code path serves every N, up to the int32 pair
    offsets.  Ragged N is handled by the same zero-pad + index-mask rule
    as the counts.  With ``hilbert_order=True`` the join runs on
    Hilbert-sorted points and the flatten maps the emitted indices back
    through the (cached) permutation, so pairs always refer to the
    original point order.

    ``mesh=`` (a 1-D mesh from ``repro.launch.mesh.make_app_mesh``) runs
    the halo join over the devices: the points are sharded by tile, each
    kept tile pair runs on the shard owning its i tile, and the foreign
    tiles it reads arrive as boundary strips by ``ppermute``.  The result
    is array-equal to the single-chip output
    (see :mod:`repro.kernels.sharded`).  No pass keeps a data-sized
    buffer in VMEM, so the join has no VMEM-budget fallback.

    The output size is data-dependent, so this wrapper host-syncs the
    kept tile-pair count and the pass-1 totals between dispatches — it
    cannot run under an outer ``jax.jit`` (P must be concrete), which is
    inherent to any exact-size join output.
    """
    ch = _app_choice(choice, "simjoin_pairs", x)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            bp = ch.block[0]
    with span("simjoin.pairs", join=count("simjoin.joins") - 1):
        if mesh is not None:
            from .sharded import simjoin_pairs_sharded

            return simjoin_pairs_sharded(
                x, eps, mesh=mesh, curve=curve, bp=bp,
                hilbert_order=hilbert_order, interpret=interpret,
            )
        if x.shape[0] == 0:
            return jnp.zeros((0, 2), dtype=jnp.int32)
        xp, bp, n_valid, perm, sched, kept = simjoin_inputs(
            x, eps, curve=curve, bp=bp, hilbert_order=hilbert_order
        )
        # the two-pass hits → non-empty tiles → emit machinery is the
        # shared driver (kernels/simjoin.py), reused by the streaming
        # join's per-tick probe dispatch (serve/apps.py)
        return simjoin_pairs_scheduled(
            sched, xp, eps=float(eps), bp=bp, n_valid=n_valid,
            interpret=_interpret(interpret), steps=kept, perm=perm,
        )


def floyd_warshall(
    d: jax.Array,
    *,
    b: int = 128,
    curve: str = "hilbert",
    fused: bool = True,
    choice=None,
    interpret: bool | None = None,
) -> jax.Array:
    """All-pairs shortest paths over an (n, n) adjacency matrix.

    ``choice`` (``None`` | ``"auto"`` | a ``phased:fw``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and ``b``
    as one tunable value (autotuner contract; defaults on a miss).

    ``fused=True`` (default) runs the phase-fused single-``pallas_call``
    kernel; ``fused=False`` the per-k-block reference (bit-identical).
    Any n is accepted: a block size is auto-picked
    (largest divisor of n that is a multiple of 8 near ``b``, else the
    matrix is padded with unreachable +inf border nodes and the result
    sliced back).
    """
    n = d.shape[0]
    ch = _app_choice(choice, "floyd_warshall", d)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            b = ch.block[0]
    bb, npad = _block_and_pad(n, b, mult=_FW_CHUNK)
    dp = d.astype(jnp.float32)
    if npad != n:
        dp = jnp.pad(dp, ((0, npad - n), (0, npad - n)), constant_values=jnp.inf)
        border = jnp.arange(n, npad)
        dp = dp.at[border, border].set(0.0)  # pad nodes: self-loops only
    if fused:
        # VMEM-budget gate on the fused form's 2·b·b + 2·b·n f32 scratch
        prog = fw_program(curve, npad // bb, bb)
        fused = fused_fits("floyd_warshall", prog, dp)
    fn = floyd_warshall_blocked if fused else floyd_warshall_blocked_reference
    out = fn(dp, b=bb, curve=curve, interpret=_interpret(interpret))
    return out[:n, :n] if npad != n else out


def cholesky(
    a: jax.Array,
    *,
    b: int = 128,
    curve: str = "hilbert",
    fused: bool = True,
    choice=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Lower Cholesky factor of an (n, n) SPD matrix.

    ``choice`` (``None`` | ``"auto"`` | a ``phased:cholesky``-kind
    :class:`repro.core.ScheduleChoice`) overrides ``curve`` and ``b``
    as one tunable value (autotuner contract; defaults on a miss).

    ``fused=True`` (default) runs the phase-fused single-``pallas_call``
    kernel; ``fused=False`` the per-k-block reference (bit-identical).
    Any n is accepted: a block size is auto-picked
    (largest divisor of n near ``b``, else the matrix is padded with an
    identity border — chol([[A, 0], [0, I]]) = [[L, 0], [0, I]] — and
    the factor sliced back).
    """
    n = a.shape[0]
    ch = _app_choice(choice, "cholesky", a)
    if ch is not None:
        curve = ch.curve
        if ch.block:
            b = ch.block[0]
    # mult=8 keeps auto-picked blocks aligned to Mosaic's (8, 128) tiling
    # (the fused kernel itself has no chunking constraint, the hardware does)
    bb, npad = _block_and_pad(n, b, mult=8)
    ap = a.astype(jnp.float32)
    if npad != n:
        ap = jnp.pad(ap, ((0, npad - n), (0, npad - n)))
        border = jnp.arange(n, npad)
        ap = ap.at[border, border].set(1.0)
    if fused:
        # VMEM-budget gate on the fused form's 2·b·b + b·n f32 scratch
        prog = cholesky_program(curve, npad // bb, bb)
        fused = fused_fits("cholesky", prog, ap)
    fn = cholesky_blocked if fused else cholesky_blocked_reference
    out = fn(ap, b=bb, curve=curve, interpret=_interpret(interpret))
    return out[:n, :n] if npad != n else out


__all__ = [
    "matmul",
    "attention",
    "attention_decode",
    "attention_prefill",
    "kmeans_assign",
    "kmeans_lloyd",
    "simjoin_counts",
    "simjoin_pairs",
    "floyd_warshall",
    "cholesky",
    "ref",
]
