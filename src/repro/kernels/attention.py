"""Flash attention with FGF jump-over tile scheduling (paper §6.2).

Causal attention touches only the lower-triangular half of the
(q_tile × kv_tile) grid.  The usual TPU kernel runs the full rectangular
grid and masks — paying compute and HBM traffic for tiles that contribute
nothing.  The paper's jump-over idea applies directly: enumerate *only*
the valid tiles with the FGF walker (triangle region, O(log) re-entry),
handing the kernel a scalar-prefetch schedule.  ~2× fewer grid steps at
long context.

Schedule layout int32[steps, 4]: (q_tile, kv_tile, is_first, is_last)
where first/last flag the schedule-order boundaries of each q tile's kv
visit run (the online-softmax state is init'd / finalised there).  Within
a q tile the kv tiles may be visited in any order (online softmax is
order-free); we default to *serpentine* kv order so the kv operand tile is
reused across every q-tile boundary — the boustrophedon trick, which on
this state-constrained grid is the locality maximum the Hilbert family
can reach (one register chain per q row forbids full 2-D swizzling; see
DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import register_schedule_cache

from .launch import flat_kernel, flat_table_spec, vmem_limit

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def causal_schedule(qt: int, kt_per_q, *, serpentine: bool = True) -> np.ndarray:
    """FGF jump-over schedule for causal attention tiles.

    ``kt_per_q``: either an int function-like (q -> #kv tiles) or None for
    the standard causal triangle (kv_tile <= q_tile).  Returns
    int32[steps, 4] (q, kv, first, last).
    """
    rows = []
    for q in range(qt):
        hi = q + 1 if kt_per_q is None else int(kt_per_q(q))
        kvs = list(range(hi))
        if serpentine and (q % 2 == 1):
            kvs.reverse()
        for pos, kv in enumerate(kvs):
            rows.append((q, kv, 1 if pos == 0 else 0, 1 if pos == len(kvs) - 1 else 0))
    return np.asarray(rows, dtype=np.int32)


def full_schedule(qt: int, kt: int, *, serpentine: bool = True) -> np.ndarray:
    """Non-causal (encoder) schedule: full rectangle, serpentine kv."""
    return causal_schedule(qt, lambda q: kt, serpentine=serpentine)


def _flash_kernel(
    sched_ref,
    seq_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    causal: bool,
    bq: int,
    bkv: int,
    kv_valid: int | None,
    varlen: bool,
):
    s = pl.program_id(1)
    first = sched_ref[s, 2]
    last = sched_ref[s, 3]
    q_tile = sched_ref[s, 0]
    kv_tile = sched_ref[s, 1]

    @pl.when(first == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bkv, d)
    v = v_ref[0].astype(jnp.float32)  # (bkv, d)

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

    if causal:
        # mask only matters on the diagonal tile; cheap to apply always
        q_pos = q_tile * bq + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        kv_pos = kv_tile * bkv + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(q_pos >= kv_pos, scores, DEFAULT_MASK_VALUE)

    if kv_valid is not None:
        # ragged S: kv positions past the true sequence length are zero
        # padding — mask them out of the softmax (ops.py slices the padded
        # q rows off the output)
        kv_pos = kv_tile * bkv + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(kv_pos < kv_valid, scores, DEFAULT_MASK_VALUE)

    if varlen:
        # per-sequence kv length (production padding masks, mirroring the
        # cuDNN fused-attention surface): position >= seq_ref[bh] is pad
        bh = pl.program_id(0)
        kv_pos = kv_tile * bkv + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(kv_pos < seq_ref[bh], scores, DEFAULT_MASK_VALUE)

    m_prev = m_ref[:, 0:1]  # (bq, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last == 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "bq", "bkv", "kv_valid", "interpret"),
)
def flash_attention_swizzled(
    schedule: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    bq: int = 128,
    bkv: int = 128,
    kv_valid: int | None = None,
    kv_seqlen: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention over (BH, S, D) tensors with a jump-over tile schedule.

    q/k/v: (BH, S, D) — batch*heads flattened (GQA expansion in ops.py).
    ``kv_valid``: true sequence length when S carries block padding; kv
    positions >= kv_valid are masked out of the softmax (static — one
    length for the whole batch).  ``kv_seqlen``: int32[BH] *per-sequence*
    valid lengths (dynamic — a scalar-prefetch operand, so one compiled
    program serves every padding pattern); q rows at positions >=
    their sequence's length see an all-masked row and are undefined —
    mask or slice them off (``ops.attention`` zeroes them via
    ``q_seqlen``).
    """
    BH, S, D = q.shape
    assert k.shape == v.shape == (BH, S, D)
    assert S % bq == 0 and S % bkv == 0
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    steps = schedule.shape[0]
    varlen = kv_seqlen is not None
    if not varlen:
        # constant-arity prefetch: a dummy length operand keeps ONE kernel
        # signature; varlen=False skips its mask entirely (bit-identical
        # to the pre-varlen program)
        kv_seqlen = jnp.full((BH,), S, dtype=jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, steps),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, s, sr, sq: (bh, sr[s, 0], 0)),
            pl.BlockSpec((1, bkv, D), lambda bh, s, sr, sq: (bh, sr[s, 1], 0)),
            pl.BlockSpec((1, bkv, D), lambda bh, s, sr, sq: (bh, sr[s, 1], 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, s, sr, sq: (bh, sr[s, 0], 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bkv=bkv,
            kv_valid=kv_valid, varlen=varlen,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(schedule, jnp.asarray(kv_seqlen, dtype=jnp.int32), q, k, v)


# ---------------------------------------------------------------------------
# paged decode
# ---------------------------------------------------------------------------

def decode_page_schedule(
    num_slots: int, max_pages: int, slot_order: tuple[int, ...] | None = None
) -> np.ndarray:
    """Schedule for the paged decode kernel: int32[steps, 4] rows of
    (slot, logical_page, first, last).

    Every slot visits its logical pages 0..max_pages-1 in order (the
    online-softmax run per slot; first/last flag its boundaries).  Pages
    past a slot's live length still appear — the kernel masks them by the
    slot's position, so ONE static schedule serves every ragged fill
    state (continuous batching: each slot is at a different depth).
    Physical placement is the page table's job, not the schedule's: the
    allocator lays (slot, page) out along the registry's Hilbert map
    (:mod:`repro.serve.kv_pages`), so this logical walk gathers few,
    long physical runs.
    """
    order = range(num_slots) if slot_order is None else slot_order
    rows = []
    for slot in order:
        for lp in range(max_pages):
            rows.append(
                (slot, lp, 1 if lp == 0 else 0, 1 if lp == max_pages - 1 else 0)
            )
    return np.asarray(rows, dtype=np.int32)


@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _decode_page_schedule_cached(
    num_slots: int, max_pages: int, slot_order: tuple[int, ...] | None = None
) -> np.ndarray:
    return decode_page_schedule(num_slots, max_pages, slot_order)


@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _decode_page_schedule_dev(
    num_slots: int,
    max_pages: int,
    slot_order: tuple[int, ...] | None,
    backend: str,
) -> jax.Array:
    # materialise eagerly so the cached value is a concrete device
    # array, not a leaked tracer — a first call from inside a jit/scan
    # trace would otherwise pin the tracer for every later caller
    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            _decode_page_schedule_cached(num_slots, max_pages, slot_order),
            dtype=jnp.int32,
        )


def decode_page_schedule_device(
    num_slots: int, max_pages: int, slot_order: tuple[int, ...] | None = None
) -> jax.Array:
    """:func:`decode_page_schedule` as a *device* array, LRU-cached per
    (num_slots, max_pages, slot_order, backend) — the schedule is
    static over every ragged fill state, so re-uploading the host table
    each decode tick was a pure per-tick tax.
    ``jax.ensure_compile_time_eval`` makes the cached value concrete
    even when the first call happens under a jit trace."""
    if slot_order is not None:
        slot_order = tuple(int(s) for s in slot_order)
    return _decode_page_schedule_dev(
        num_slots, max_pages, slot_order, jax.default_backend()
    )


def _flat_grid_spec(cols, *, grid, in_specs, out_specs, scratch_shapes):
    """Grid spec for the paged kernels: three scalar-prefetch operands
    (schedule, page table, positions), the schedule flattened to 1-D so
    SMEM does not pad each row to 128 words (see kernels/launch.py)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[flat_table_spec(sp, cols, len(grid)) for sp in in_specs],
        out_specs=flat_table_spec(out_specs, cols, len(grid)),
        scratch_shapes=scratch_shapes,
    )


def _flash_decode_kernel(
    sched_ref,
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
):
    s = pl.program_id(1)
    slot = sched_ref[s, 0]
    lp = sched_ref[s, 1]
    first = sched_ref[s, 2]
    last = sched_ref[s, 3]

    @pl.when(first == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (g, Dk)
    k = k_ref[0, 0].astype(jnp.float32)  # (ps, Dk)
    v = v_ref[0, 0].astype(jnp.float32)  # (ps, Dv)

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

    # per-slot ragged masking: the token at pos[slot] is already written
    # (decode writes the new K/V entry before attending, like the dense
    # path), so <= is the inclusive bound.  Everything past it — the tail
    # of the current page, stale contents of a recycled page, and whole
    # unallocated pages (their table entries point at the reserved trash
    # page 0) — is masked out of the softmax.
    kv_pos = lp * page_size + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(kv_pos <= pos_ref[slot], scores, DEFAULT_MASK_VALUE)

    m_prev = m_ref[:, 0:1]  # (g, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last == 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def flash_attention_decode(
    schedule: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One decode step of attention against a PAGED KV cache.

    q: (B, Hkv, g, Dk) — the B slots' single-token queries, grouped GQA
    layout (g = H // Hkv query heads share each KV head; MLA passes
    Hkv=1, g=H and its concatenated latent ⊕ rope width as Dk).
    k_pages/v_pages: (P, Hkv, page_size, Dk/Dv) physical page pools —
    head-major inside a page, so one (page, head) block is a contiguous
    (page_size, D) tile whose last two dims are the array's own (the
    TPU block-tiling rule).
    page_table: int32[B, max_pages] logical→physical page map (dynamic —
    scalar-prefetched, so allocation churn never recompiles).
    pos: int32[B] per-slot positions; the entry at pos is live, later
    positions are masked.  schedule: :func:`decode_page_schedule`.

    Grid is (Hkv, steps); each schedule step DMAs exactly one physical
    page per pool — the index map reads the page table, so the gather's
    HBM access stream IS the allocator's physical layout.  Returns
    (B, Hkv, g, Dv).
    """
    B, Hkv, g, Dk = q.shape
    P, Hkv_k, ps, Dk_k = k_pages.shape
    Dv = v_pages.shape[-1]
    assert (Hkv_k, Dk_k) == (Hkv, Dk), (k_pages.shape, q.shape)
    assert v_pages.shape[:3] == (P, Hkv, ps), v_pages.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dk))
    steps, cols = schedule.shape

    grid_spec = _flat_grid_spec(
        cols,
        grid=(Hkv, steps),
        in_specs=[
            pl.BlockSpec((1, 1, g, Dk), lambda h, s, sr, pt, pv: (sr[s, 0], h, 0, 0)),
            pl.BlockSpec(
                (1, 1, ps, Dk),
                lambda h, s, sr, pt, pv: (pt[sr[s, 0], sr[s, 1]], h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, Dv),
                lambda h, s, sr, pt, pv: (pt[sr[s, 0], sr[s, 1]], h, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, Dv), lambda h, s, sr, pt, pv: (sr[s, 0], h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, Dv), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        flat_kernel(
            functools.partial(_flash_decode_kernel, sm_scale=sm_scale, page_size=ps),
            cols,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(
        schedule.reshape(-1),
        jnp.asarray(page_table, dtype=jnp.int32),
        jnp.asarray(pos, dtype=jnp.int32),
        q,
        k_pages,
        v_pages,
    )


# ---------------------------------------------------------------------------
# paged prefill (PR 10): batched causal attention over whole prompts
# ---------------------------------------------------------------------------

def prefill_page_schedule(
    pos0,
    n_new,
    page_size: int,
    max_pages: int,
    bq: int | None = None,
) -> np.ndarray:
    """Schedule for the paged prefill kernel: int32[steps, 6] rows of
    (slot, q_tile, logical_page, first, last, valid).

    Unlike the decode schedule this one IS ragged-shaped: each slot
    contributes ``ceil(n_new/bq)`` q tiles, and q tile ``t`` visits
    logical pages ``0..(last position in the tile) // page_size`` — the
    causal triangle at page granularity, so total work is O(prompt)
    pages per slot instead of the O(prompt²) masked-decode walk.  Slots
    with ``n_new == 0`` (inactive lanes riding along in the batch)
    contribute nothing.  Steps are padded to the next power of two with
    ``valid=0`` rows the kernel skips, so same-bucket cohorts share one
    compiled program (the schedule itself is a dynamic scalar-prefetch
    operand).  A pad row repeats the last real row's (slot, q_tile,
    page) with every flag 0: its output block index is the last one
    written, so the pipeline's final write-back carries that block's
    finished values — a pad row pointing anywhere else would write back
    a VMEM buffer the kernel never filled (the TPU pipeline does not
    re-fetch output blocks).
    """
    bq = page_size if bq is None else bq
    rows = []
    for slot, (p0, nn) in enumerate(zip(pos0, n_new)):
        p0, nn = int(p0), int(nn)
        if nn <= 0:
            continue
        n_qt = -(-nn // bq)
        for qt in range(n_qt):
            q_hi = p0 + min((qt + 1) * bq, nn) - 1  # last live q position
            lp_hi = min(q_hi // page_size, max_pages - 1)
            for lp in range(lp_hi + 1):
                rows.append(
                    (slot, qt, lp, 1 if lp == 0 else 0,
                     1 if lp == lp_hi else 0, 1)
                )
    if not rows:
        rows = [(0, 0, 0, 0, 0, 0)]
    out = np.asarray(rows, dtype=np.int32)
    steps = out.shape[0]
    bucket = 1 << max(steps - 1, 0).bit_length()
    if bucket != steps:
        pad = np.zeros((bucket - steps, 6), dtype=np.int32)
        pad[:, :3] = out[-1, :3]
        out = np.concatenate([out, pad], axis=0)
    return out


@register_schedule_cache
@functools.lru_cache(maxsize=128)
def _prefill_page_schedule_dev(
    pos0: tuple, n_new: tuple, page_size: int, max_pages: int, bq: int,
    backend: str,
) -> jax.Array:
    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            prefill_page_schedule(pos0, n_new, page_size, max_pages, bq),
            dtype=jnp.int32,
        )


def prefill_page_schedule_device(
    pos0, n_new, page_size: int, max_pages: int, bq: int | None = None
) -> jax.Array:
    """:func:`prefill_page_schedule` as a device array (LRU per cohort
    shape + backend, concrete even under a trace)."""
    bq = page_size if bq is None else bq
    return _prefill_page_schedule_dev(
        tuple(int(p) for p in pos0),
        tuple(int(n) for n in n_new),
        page_size,
        max_pages,
        bq,
        jax.default_backend(),
    )


def _flash_prefill_kernel(
    sched_ref,
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
    bq: int,
    g: int,
):
    s = pl.program_id(1)
    slot = sched_ref[s, 0]
    qt = sched_ref[s, 1]
    lp = sched_ref[s, 2]
    first = sched_ref[s, 3]
    last = sched_ref[s, 4]
    valid = sched_ref[s, 5]

    @pl.when((first == 1) & (valid == 1))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(valid == 1)
    def _step():
        # (bq, g, Dk) -> (bq*g, Dk): row r is query token r // g, head
        # r % g — a plain 2-D matmul the MXU can take directly
        q = q_ref[0, :, 0].astype(jnp.float32).reshape(bq * g, -1)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, Dk)
        v = v_ref[0, 0].astype(jnp.float32)  # (ps, Dv)

        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

        # causal + ragged mask in one comparison: query token i of tile
        # qt sits at absolute position pos0[slot] + qt*bq + i and may
        # see kv positions <= its own (the whole cohort's new K/V is
        # scattered before this kernel runs, so self-attention is
        # write-before-attend like the decode path).  Padded q rows
        # (i >= n_new) sit at future positions; their output is garbage
        # the caller discards, but stays finite (mask value is finite).
        tok = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // g
        q_pos = pos_ref[slot] + qt * bq + tok
        kv_pos = lp * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        scores = jnp.where(kv_pos <= q_pos, scores, DEFAULT_MASK_VALUE)

        m_prev = m_ref[:, 0:1]  # (bq*g, 1)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when((last == 1) & (valid == 1))
    def _flush():
        out = acc_ref[...] / l_ref[:, 0:1]
        o_ref[0, :, 0] = out.reshape(bq, g, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def flash_attention_prefill(
    schedule: jax.Array,
    page_table: jax.Array,
    pos0: jax.Array,
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched causal prefill attention against a PAGED KV cache.

    q: (B, Tq, Hkv, g, Dk) — each slot's Tq new prompt tokens in
    grouped GQA layout (token i lives at absolute position
    ``pos0[slot] + i``; rows at i >= the slot's new-token count are
    padding whose output is undefined-but-finite).  Tq must be a
    multiple of the page size (q tiles align to kv pages).
    k_pages/v_pages: (P, Hkv, page_size, D) physical pools (the decode
    kernel's layout) with the cohort's new K/V already
    scattered through the page table (split-phase: XLA scatter first,
    then this kernel gathers — no write-then-read hazard inside the
    pipeline).  schedule: :func:`prefill_page_schedule`, a dynamic
    scalar-prefetch operand.  Returns (B, Tq, Hkv, g, Dv).
    """
    B, Tq, Hkv, g, Dk = q.shape
    P, Hkv_k, ps, Dk_k = k_pages.shape
    Dv = v_pages.shape[-1]
    assert (Hkv_k, Dk_k) == (Hkv, Dk), (k_pages.shape, q.shape)
    assert Tq % ps == 0, (Tq, ps)
    bq = ps
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(Dk))
    steps, cols = schedule.shape

    grid_spec = _flat_grid_spec(
        cols,
        grid=(Hkv, steps),
        in_specs=[
            pl.BlockSpec(
                (1, bq, 1, g, Dk),
                lambda h, s, sr, pt, pv: (sr[s, 0], sr[s, 1], h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, Dk),
                lambda h, s, sr, pt, pv: (pt[sr[s, 0], sr[s, 2]], h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, Dv),
                lambda h, s, sr, pt, pv: (pt[sr[s, 0], sr[s, 2]], h, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, 1, g, Dv),
            lambda h, s, sr, pt, pv: (sr[s, 0], sr[s, 1], h, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq * g, Dv), jnp.float32),
            pltpu.VMEM((bq * g, 128), jnp.float32),
            pltpu.VMEM((bq * g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        flat_kernel(
            functools.partial(
                _flash_prefill_kernel,
                sm_scale=sm_scale,
                page_size=ps,
                bq=bq,
                g=g,
            ),
            cols,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tq, Hkv, g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                # double-buffered q / out / page blocks + f32 softmax state
                2 * bq * g * (Dk * q.dtype.itemsize + Dv * q.dtype.itemsize)
                + 2 * ps * (Dk + Dv) * k_pages.dtype.itemsize
                + 4 * bq * g * (Dv + 2 * 128 + 2 * ps)
            ),
        ),
        interpret=interpret,
        name="flash_prefill",
    )(
        schedule.reshape(-1),
        jnp.asarray(page_table, dtype=jnp.int32),
        jnp.asarray(pos0, dtype=jnp.int32),
        q,
        k_pages,
        v_pages,
    )
