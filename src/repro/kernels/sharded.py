"""shard_map scale-out of the data-mining apps (curve-range partitioned).

The execution layer makes this almost declarative: the same schedule
tables that drive the fused single-core kernels drive the device mesh.
Shards are contiguous ranges of an already-curve-ordered schedule — for
k-means contiguous runs of (Hilbert-sorted) point tiles, for the ε-join
contiguous runs of FGF-Hilbert triangle tile pairs — so every shard
works a compact, low-surface region of the problem (the paper's
locality argument applied to the mesh instead of the cache).  The
contract of such a partition (disjoint, covering, contiguous in Hilbert
order) is :func:`repro.core.curve_partition`; the apps use its
SPMD-uniform specialisation — equal-length ranges, the tail padded with
inert rows — because ``shard_map`` traces ONE program for all shards
and therefore needs equal shapes.

**k-means** (:func:`kmeans_lloyd_sharded`): every device runs the
shard-local Lloyd-step program (phase-fused assign + per-tile update
partials, ONE pallas dispatch per iteration per shard) under
``shard_map`` with the iteration loop in ``lax.scan``.  Cross-shard
reduction is split by exactness class:

* counts are integer-valued f32, so a plain ``psum`` is EXACT under any
  reduction grouping — the psum'd count accumulator of the issue;
* the f32 coordinate sums are NOT association-free, so ``reduce``
  selects an exactness class: ``"exact"`` (default) ``all_gather``\\ s
  the per-tile partials and folds them in the *single-core fused
  kernel's own accumulation order* (the phase-1 first-appearance order
  of the global schedule) — BIT-identical to single-core on any mesh
  size; ``"tree"`` folds locally then combines shards through a fixed
  recursive-doubling butterfly (deterministic association ⇒ bit-stable
  run to run, O(K·D·log S) bytes, allclose to single-core);
  ``"psum"`` leaves the association to the compiler (cheapest).

**ε-join** (:func:`simjoin_pairs_sharded`): the distributed two-pass
join, in two data-distribution modes.  Both share the schedule split:
pass 1 counts hits over each shard's curve range of the triangle
schedule; the host reads the per-step totals (the single-core path
already host-syncs here — output size is data-dependent) and keeps the
non-empty rows; pass 2 has each shard write the packed hit rows of its
non-empty rows, which one exact-size flatten kernel (reading them in the
global row order in the halo case) turns into the global pair list **in
exactly the single-core emission order** (shards hold contiguous
schedule ranges of the global pruned triangle).

* ``halo=True`` (default): x is POINT-sharded ``P(axis, None)``.  The
  ε-pruned schedule (tile reach from :func:`repro.core.
  neighbor_tile_mask` on Hilbert key ranges, or bounding-box gaps)
  assigns each triangle row to the owner of its i-tile; the foreign
  j-tiles each shard still needs are ``ppermute``\\ d in as boundary
  strips into a fixed-size halo buffer (uniform across shards — SPMD).
  Pass 2 reuses pass 1's buffer output, so each strip moves once.
  Collective bytes scale with the boundary area, not N.
* ``halo=False``: the PR-5 path — x fully replicated to every shard,
  zero jaxpr collectives; the replication itself is the (O(N·D) per
  shard) cost, which :func:`simjoin_sharded_volume` accounts.

Both wrappers reproduce the single-core wrappers' padding/tiling
decisions bit-for-bit (same ``bp`` clamp, same zero-pad + index-mask
rule, same ``kmeans_init`` centroids), which is what the differential
tests in tests/test_apps_sharded.py assert across mesh sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (
    curve_partition,
    hilbert_encode_nd,
    kmeans_schedule,
    kmeans_schedule_device,
    neighbor_tile_mask,
    register_schedule_cache,
    triangle_schedule,
)
from repro.core.tracing import count

from .kmeans import (
    _quantise_points,
    centroid_norms,
    hilbert_point_order_cached,
    kmeans_init,
    kmeans_shard_program,
    lloyd_update,
)
from .launch import collective_volume, launch, resolve_interpret
from .simjoin import (
    check_pair_offsets,
    pairs_from_masks,
    simjoin_emit_program,
    simjoin_hits_rows_program,
)

__all__ = [
    "kmeans_lloyd_sharded",
    "kmeans_sharded_collectives",
    "kmeans_sharded_volume",
    "mesh_axis",
    "simjoin_pairs_sharded",
    "simjoin_sharded_volume",
]


def mesh_axis(mesh) -> tuple[str, int]:
    """(axis name, size) of the single axis a sharded app runs over."""
    if mesh.devices.ndim != 1 or len(mesh.axis_names) != 1:
        raise ValueError(
            "sharded apps expect a 1-D mesh (see launch.mesh.make_app_mesh); "
            f"got shape {mesh.devices.shape} axes {mesh.axis_names}"
        )
    return mesh.axis_names[0], int(mesh.devices.size)


# ---------------------------------------------------------------------------
# k-means
# ---------------------------------------------------------------------------

def _tree_reduce(v: jax.Array, axis: str, num: int) -> jax.Array:
    """Hierarchical fixed-topology sum across the mesh — deterministic
    association at every mesh size, so results are bit-stable run to run
    (but NOT bit-identical to the single-core left fold: the grouping
    differs — see DESIGN.md §Halo-exchange, exactness classes).

    Power-of-two meshes run a recursive-doubling butterfly: at round r,
    partners ``ppermute`` their partials and both add (lower index
    first), so O(K·D·log S) bytes replace the exact path's O(K·D·S)
    ``all_gather``.  Other sizes ``all_gather`` the per-shard partials
    (already locally folded — S rows, not the exact path's global tile
    count) and fold a static balanced binary tree.
    """
    if num == 1:
        return v
    if num & (num - 1) == 0:
        idx = jax.lax.axis_index(axis)
        r = 1
        while r < num:
            other = jax.lax.ppermute(
                v, axis, perm=[(i, i ^ r) for i in range(num)]
            )
            low = (idx & r) == 0
            a = jnp.where(low, v, other)
            b = jnp.where(low, other, v)
            v = a + b
            r <<= 1
        return v
    g = jax.lax.all_gather(v, axis, axis=0)  # (num, ...)
    vals = [g[i] for i in range(num)]
    while len(vals) > 1:
        vals = [
            vals[i] + vals[i + 1] if i + 1 < len(vals) else vals[i]
            for i in range(0, len(vals), 2)
        ]
    return vals[0]


@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _lloyd_fn(mesh, axis, *, curve, iters, pt, ptl, ct, bp, bc, D,
              interpret, reduce):
    """Jitted shard_map Lloyd driver for one static configuration.

    ``pt`` is the global (unsharded) point-tile count, ``ptl`` the
    per-shard tile count (``ptl * S >= pt``; tiles past ``pt`` are pure
    padding and excluded from the exact fold).  ``reduce`` picks the
    coordinate-sum exactness class: ``"exact"`` (bit-identical global
    left fold), ``"tree"`` (deterministic fixed-topology tree) or
    ``"psum"`` (plain psum).  LRU-cached so warm calls reuse the
    compiled executable; registered with the schedule-cache registry
    because the captured tables derive from the curve registry.
    """
    Kp = ct * bc
    sched = kmeans_schedule_device(curve, ptl, ct)
    host = kmeans_schedule(curve, pt, ct)
    # the single-core fused kernel's accumulation order: phase-1 rows
    # visit point tiles in phase-0 first-appearance order
    order = np.ascontiguousarray(host[host[:, 0] == 1][:, 1].astype(np.int32))
    program_args = dict(pt=ptl, ct=ct, bp=bp, bc=bc, D=D)
    _, num = mesh_axis(mesh)

    def body(xT_l, c0, lim):
        program = kmeans_shard_program(sched, **program_args)

        def step(carry, _):
            c, _assign = carry
            _min_m, arg, psums, pcnts = launch(
                program, xT_l, c, centroid_norms(c), lim, interpret=interpret
            )
            # counts: integer-valued f32 — psum is exact in any grouping
            cnt = jax.lax.psum(jnp.sum(pcnts, axis=0), axis)  # (1, Kp)
            if reduce == "exact":
                # sums: reproduce the fused kernel's left fold over the
                # global per-tile partials, in its own phase-1 order
                gsums = jax.lax.all_gather(psums, axis, axis=0, tiled=True)
                ordered = gsums[jnp.asarray(order)]  # drops pure-pad tiles
                sums, _ = jax.lax.scan(
                    lambda acc, p: (acc + p, None), ordered[0], ordered[1:]
                )
            elif reduce == "tree":
                # local left fold over this shard's per-tile partials in
                # local tile order (pure-pad tiles add exact zeros), then
                # the fixed-topology cross-shard tree
                local, _ = jax.lax.scan(
                    lambda acc, p: (acc + p, None), psums[0], psums[1:]
                )
                sums = _tree_reduce(local, axis, num)
            else:  # "psum"
                sums = jax.lax.psum(jnp.sum(psums, axis=0), axis)
            return (lloyd_update(c, sums, cnt), arg.reshape(-1)), None

        init = (c0.astype(jnp.float32), jnp.zeros((xT_l.shape[1],), jnp.int32))
        (c, assign), _ = jax.lax.scan(step, init, None, length=iters)
        return c, assign

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, None), P(axis, None)),
        out_specs=(P(None, None), P(axis)),
        check_vma=False,
    )
    return jax.jit(fn)


def _resolve_reduce(exact: bool, reduce: str | None) -> str:
    """Map the legacy ``exact`` bool plus the new ``reduce`` override to
    one of the three reduction classes."""
    if reduce is None:
        return "exact" if exact else "psum"
    if reduce not in ("exact", "tree", "psum"):
        raise ValueError(
            f"reduce must be 'exact', 'tree' or 'psum'; got {reduce!r}"
        )
    return reduce


def _lloyd_setup(
    x, k, *, iters, curve, seed, bp, bc, hilbert_order, interpret, mesh, reduce
):
    """Shared host-side prep: mirrors ops.kmeans_lloyd's single-core
    decisions (clamped blocks, zero-pad + index-mask, shared c0), then
    pads the tile count to a multiple of the mesh size."""
    N, D = x.shape
    c0 = kmeans_init(x, k, seed)
    inv = None
    if hilbert_order:
        perm = hilbert_point_order_cached(x)
        inv = jnp.argsort(perm)
        x = x[perm]
    bp, bc = min(bp, N), min(bc, k)
    pt = -(-N // bp)
    axis, num = mesh_axis(mesh)
    # SPMD-uniform curve-range partition: every shard as wide as the
    # largest curve_partition range (= ceil), the tail pure padding
    ptl = int(np.diff(curve_partition(pt, num)).max())
    Nl = ptl * bp
    Np = Nl * num
    xp = jnp.pad(x, ((0, Np - N), (0, 0))) if Np != N else x
    pc = (-k) % bc
    cp = jnp.pad(c0, ((0, pc), (0, 0))) if pc else c0
    ct = cp.shape[0] // bc
    limits = np.stack(
        [np.clip(N - np.arange(num) * Nl, 0, Nl), np.full(num, k)], axis=1
    ).astype(np.int32)
    fn = _lloyd_fn(
        mesh, axis, curve=curve, iters=iters, pt=pt, ptl=ptl, ct=ct,
        bp=bp, bc=bc, D=D, interpret=resolve_interpret(interpret),
        reduce=reduce,
    )
    return fn, (xp.T, cp, jnp.asarray(limits)), (inv, N, k)


def kmeans_lloyd_sharded(
    x: jax.Array,
    k: int,
    *,
    mesh,
    iters: int = 10,
    curve: str = "fur",
    seed: int = 0,
    bp: int = 256,
    bc: int = 128,
    hilbert_order: bool = False,
    exact: bool = True,
    reduce: str | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Lloyd k-means over a device mesh, curve-range sharded point tiles.

    Returns (centroids f32[k, D], assignment int32[N]).  The centroid
    coordinate-sum reduction comes in three exactness classes, picked by
    ``reduce`` (``exact`` is the legacy bool alias: True → ``"exact"``,
    False → ``"psum"``; an explicit ``reduce`` wins):

    * ``"exact"`` (default): BIT-identical to
      ``ops.kmeans_lloyd(..., fused=True)`` on any mesh size — global
      per-tile partials are ``all_gather``\\ ed and left-folded in the
      fused kernel's own order.  O(K·D·S·tiles) bytes.
    * ``"tree"``: hierarchical fixed-topology reduction — local left
      fold per shard, then a recursive-doubling butterfly (power-of-two
      meshes; O(K·D·log S) bytes) or a static balanced pairwise tree.
      Deterministic fold order ⇒ bit-stable across runs at every mesh
      size, but NOT bit-identical to the single-core left fold (the
      association differs; allclose).
    * ``"psum"``: plain ``psum`` — cheapest, association up to the
      compiler (allclose, no determinism contract).

    One pallas dispatch per iteration per shard; counts always reduce by
    ``psum`` (integer-valued f32 — exact in any grouping).
    """
    fn, args, (inv, N, k) = _lloyd_setup(
        x, k, iters=iters, curve=curve, seed=seed, bp=bp, bc=bc,
        hilbert_order=hilbert_order, interpret=interpret, mesh=mesh,
        reduce=_resolve_reduce(exact, reduce),
    )
    c, assign = fn(*args)
    c, assign = c[:k], assign[:N]
    if inv is not None:
        assign = assign[inv]
    return c, assign


def kmeans_sharded_collectives(
    x,
    k,
    *,
    mesh,
    iters: int = 10,
    curve: str = "fur",
    seed: int = 0,
    bp: int = 256,
    bc: int = 128,
    hilbert_order: bool = False,
    exact: bool = True,
    reduce: str | None = None,
    interpret: bool | None = None,
) -> dict[str, int]:
    """Collective-primitive counts of the sharded Lloyd program (traced,
    not run) — the communication structure ``bench_apps`` records next
    to the wall clock.  Counts are per compiled program; collectives
    inside the scanned step body execute once per iteration."""
    from .launch import count_collectives

    fn, args, _ = _lloyd_setup(
        x, k, iters=iters, curve=curve, seed=seed, bp=bp, bc=bc,
        hilbert_order=hilbert_order, interpret=interpret, mesh=mesh,
        reduce=_resolve_reduce(exact, reduce),
    )
    return count_collectives(fn, *args)


def kmeans_sharded_volume(
    x,
    k,
    *,
    mesh,
    iters: int = 10,
    curve: str = "fur",
    seed: int = 0,
    bp: int = 256,
    bc: int = 128,
    hilbert_order: bool = False,
    exact: bool = True,
    reduce: str | None = None,
    interpret: bool | None = None,
) -> dict:
    """Collective *volume* of the sharded Lloyd program (traced, not
    run): executed counts + modelled bytes per shard, including the
    ``P(None, None)`` centroid replication (no collective in the jaxpr,
    but every shard receives the full centroid block)."""
    fn, args, _ = _lloyd_setup(
        x, k, iters=iters, curve=curve, seed=seed, bp=bp, bc=bc,
        hilbert_order=hilbert_order, interpret=interpret, mesh=mesh,
        reduce=_resolve_reduce(exact, reduce),
    )
    return collective_volume(fn, *args, replicated_bytes=args[1].nbytes)


# ---------------------------------------------------------------------------
# ε-join (distributed two-pass pair emission)
# ---------------------------------------------------------------------------

@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _join_pass1_fn(mesh, axis, *, eps, bp, D, n_valid, interpret):
    # rows-only program: the column partials of the full hits program are
    # dead in the two-pass join, so the shard_map must not materialise
    # (and un-shard) a second per-shard (steps, bp) array
    def body(sched_l, x):
        program = simjoin_hits_rows_program(
            sched_l, eps=eps, bp=bp, D=D, n_valid=n_valid
        )
        return launch(program, x, x.T, interpret=interpret)[:, 0]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None),
        check_vma=False,
    )
    return jax.jit(fn)


@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _join_pass2_fn(mesh, axis, *, eps, bp, D, n_valid, halo, interpret):
    """Pass 2 on every shard: the packed hit rows and row counts of its
    live table rows (``halo`` picks the 5-col slot/global table over a
    resident+halo buffer instead of the 3-col table over the replicated
    points)."""

    def body(table_l, x):
        program = simjoin_emit_program(
            table_l, eps=eps, bp=bp, D=D, n_valid=n_valid, halo=halo,
        )
        return tuple(launch(program, x, x.T, interpret=interpret))

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None) if halo else P(None, None)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


# --- halo exchange: boundary strips instead of full replication ----------

@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _halo_pass1_fn(mesh, axis, *, eps, bp, D, n_valid, plan, interpret):
    """Point-sharded pass 1: neighbour-exchange the boundary strips named
    by the curve calculus, then count hits on resident+halo tiles.

    ``plan`` is the static exchange topology — a tuple of ``(delta, m)``
    ring entries: every shard sends ``m`` of its resident tiles (indices
    in its send table) to the shard ``delta`` above it.  Returns the
    per-row hit sums AND the assembled per-shard buffer so pass 2 reuses
    it without a second exchange.
    """
    _, num = mesh_axis(mesh)

    def body(sched_l, x_l, *send_idx):
        xt = x_l.reshape(-1, bp, D)  # (ptl, bp, D) resident tiles
        strips = []
        for (delta, _m), idx in zip(plan, send_idx):
            sel = jnp.take(xt, idx[0], axis=0)
            pairs = [(j, j + delta) for j in range(num - delta)]
            strips.append(jax.lax.ppermute(sel, axis, perm=pairs))
        buf = jnp.concatenate([xt, *strips], axis=0) if strips else xt
        buf = buf.reshape(-1, D)
        program = simjoin_hits_rows_program(
            sched_l, eps=eps, bp=bp, D=D, n_valid=n_valid, halo=True
        )
        return launch(program, buf, buf.T, interpret=interpret)[:, 0], buf

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None))
        + tuple(P(axis, None) for _ in plan),
        out_specs=(P(axis, None), P(axis, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def _tile_reach(x, pt: int, bp: int, eps: float, sorted_keys: bool):
    """Conservative bool[pt, pt] tile reach mask: False only where NO
    point pair of the two tiles can be within ``eps``.

    ``sorted_keys=True`` (points are Hilbert-sorted): per-tile sort-key
    ranges + :func:`repro.core.neighbor_tile_mask` on the quantised grid
    — the curve-neighbour calculus, with ε converted to cell widths plus
    half a cell of float-quantisation slack.  Otherwise (arbitrary point
    order, tiles are not spatially compact in general): per-tile bounding
    boxes on ALL features, box gap ≤ ε (with a relative f32 slack for
    the kernel's float distance).
    """
    x = np.asarray(x)
    N, D = x.shape
    if sorted_keys and min(D, 3) >= 2:
        q, nb = _quantise_points(jnp.asarray(x))
        qn = np.asarray(q, dtype=np.int64)
        d = qn.shape[1]
        keys = np.atleast_1d(np.asarray(hilbert_encode_nd(qn, nb)))
        xf = x[:, :d].astype(np.float64)
        span = np.maximum(xf.max(axis=0) - xf.min(axis=0), 1e-9)
        radius = float(eps) * float((((1 << nb) - 1) / span).max()) + 0.5
        # The tree walk is O(boundary cells), so at fine nbits a large ε
        # names millions of cells.  Coarsen in d-level steps (the
        # canonical codec is self-similar at multiples of d: high key
        # bits ARE the coarse curve index) until the radius spans only a
        # few cells.  Minimum cell gaps scale exactly by 2^s, so the
        # coarse mask remains conservative — merely less selective.
        s = 0
        while nb - s > d and radius / (1 << s) > 4.0:
            s += d
        nb -= s
        keys = keys >> (d * s)
        radius = radius / (1 << s)
        kr = np.empty((pt, 2), np.int64)
        for t in range(pt):
            a, b = t * bp, min((t + 1) * bp, N)
            kr[t] = (keys[a], keys[b - 1]) if a < N else (1, 0)
        return neighbor_tile_mask(kr, ndim=d, nbits=nb, radius=radius)
    lo = np.full((pt, D), np.inf)
    hi = np.full((pt, D), -np.inf)
    for t in range(pt):
        a, b = t * bp, min((t + 1) * bp, N)
        if a < N:
            lo[t], hi[t] = x[a:b].min(axis=0), x[a:b].max(axis=0)
    live = lo[:, 0] != np.inf
    eps_eff = float(eps) * (1.0 + 1e-5) + 1e-6
    reach = np.eye(pt, dtype=bool)
    for t in range(pt):
        if not live[t]:
            continue
        g = np.maximum(np.maximum(lo[t][None, :] - hi, lo - hi[t][None, :]), 0)
        reach[t] |= live & (np.sum(g * g, axis=1) <= eps_eff * eps_eff)
    return reach | reach.T


def _halo_plan(pruned: np.ndarray, ptl: int, num: int):
    """Host-side exchange plan for a pruned triangle schedule.

    Rows go to the shard owning their *i* tile; every foreign *j* tile is
    a lower tile (``j <= i`` in the triangle), so strips only flow up the
    ring.  Returns ``(row_ids, plan, send_tables, slots, n_buf_tiles)``:
    per-shard row indices into ``pruned`` (global order preserved), the
    static ``(delta, m)`` topology, per-delta int32[num, m] sender-local
    tile tables, per-shard {global tile -> buffer slot} maps, and the
    uniform per-shard buffer size in tiles (resident ``ptl`` + halo).
    """
    owner = pruned[:, 0] // ptl
    row_ids = [np.nonzero(owner == s)[0] for s in range(num)]
    need = []
    for s in range(num):
        tj = pruned[row_ids[s], 1]
        need.append(sorted({int(t) for t in tj if t // ptl != s}))
    plan, send_tables = [], []
    slots: list[dict] = [dict() for _ in range(num)]
    base = ptl
    for delta in range(1, num):
        per_dest = [
            [t for t in need[s] if t // ptl == s - delta] for s in range(num)
        ]
        m = max(len(v) for v in per_dest)
        if m == 0:
            continue
        tbl = np.zeros((num, m), np.int32)
        for s in range(num):
            for pos, t in enumerate(per_dest[s]):
                tbl[s - delta, pos] = t - (s - delta) * ptl
                slots[s][t] = base + pos
        plan.append((delta, m))
        send_tables.append(tbl)
        base += m
    return row_ids, tuple(plan), send_tables, slots, base


def simjoin_pairs_sharded(
    x: jax.Array,
    eps: float,
    *,
    mesh,
    curve: str = "hilbert",
    bp: int = 256,
    hilbert_order: bool = False,
    halo: bool = True,
    interpret: bool | None = None,
    _volume: dict | None = None,
) -> jax.Array:
    """Distributed two-pass ε-join pair emission.  int32[P, 2], i > j.

    ``halo=True`` (default) is true distributed memory: x is
    point-sharded (``P(axis, None)``), the triangle schedule is pruned
    by the conservative tile-reach mask (curve-neighbour calculus on
    Hilbert-sorted points, bounding-box gaps otherwise), each pruned row
    runs on the shard owning its *i* tile, and the only cross-device
    data motion is a ``ppermute`` of the boundary strips the reach mask
    names — a fixed-size halo buffer per shard, reused by pass 2.
    Per-shard hit counts → the non-empty rows and the exact pair count
    on the host (the inherent host sync of an exact-size join) →
    per-shard packed hit rows of those rows → one flatten kernel in the
    global schedule order.
    Pruned rows contribute zero pairs by construction of the reach
    mask, so the result is array-equal (not just set-equal) to
    ``ops.simjoin_pairs`` on every mesh size.

    ``halo=False`` retains the replicated path (x broadcast to every
    shard, schedule rows curve-range partitioned, no collectives): the
    baseline the halo differentials and the ``bytes_per_shard`` bench
    rows compare against.
    """
    N, D = x.shape
    if N == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    perm = None
    if hilbert_order:
        perm = hilbert_point_order_cached(x)
        x = x[perm]
    bp = min(bp, N)
    pn = (-N) % bp
    xp = jnp.pad(x, ((0, pn), (0, 0))) if pn else x
    pt = xp.shape[0] // bp
    n_valid = N if pn else None
    interp = resolve_interpret(interpret)
    axis, num = mesh_axis(mesh)
    tri = np.asarray(triangle_schedule(curve, pt, strict=False))
    if halo:
        return _join_halo(
            x, xp, float(eps), mesh=mesh, axis=axis, num=num, bp=bp, D=D,
            pt=pt, n_valid=n_valid, tri=tri, sorted_keys=hilbert_order,
            interp=interp, volume=_volume, perm=perm,
        )
    return _join_replicated(
        x, xp, float(eps), mesh=mesh, axis=axis, num=num, bp=bp, D=D,
        n_valid=n_valid, tri=tri, interp=interp, volume=_volume, perm=perm,
    )


def _join_replicated(
    x, xp, eps, *, mesh, axis, num, bp, D, n_valid, tri, interp, volume, perm
):
    steps = len(tri)
    # SPMD-uniform curve-range partition of the triangle schedule's rows
    per = int(np.diff(curve_partition(steps, num)).max())
    pad_rows = per * num - steps
    tri_pad = (
        np.concatenate([tri, np.zeros((pad_rows, 2), tri.dtype)])
        if pad_rows else tri
    )

    pass1 = _join_pass1_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, interpret=interp,
    )
    sched_dev = jnp.asarray(tri_pad, dtype=jnp.int32)
    if volume is not None:
        # the replicated path has no jaxpr collectives — its per-shard
        # traffic is the P(None, None) broadcast of x into each pass
        _acc_volume(volume, pass1, sched_dev, xp, replicated=xp.nbytes)
    hits_i = pass1(sched_dev, xp)
    tot = np.asarray(jnp.sum(hits_i, axis=1)).astype(np.int64)[:steps]
    count("simjoin.tile_pairs", steps)
    count("simjoin.tiles_live", int(np.count_nonzero(tot)))
    P_total = int(tot.sum())
    if P_total == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    check_pair_offsets(P_total, bp)
    # pass 2: each shard packs the hit rows of the non-empty tiles of its
    # own contiguous range, padded to a uniform per-shard row count
    tot_pad = np.concatenate([tot, np.zeros(pad_rows, np.int64)])
    live = (tot_pad > 0).reshape(num, per)
    per2 = max(1, int(live.sum(axis=1).max()))
    table = np.zeros((num, per2, 3), np.int32)
    rows = []
    for sh in range(num):
        idx = np.nonzero(live[sh])[0]
        table[sh, : len(idx), :2] = tri_pad[sh * per + idx]
        table[sh, : len(idx), 2] = 1
        rows.append(sh * per2 + np.arange(len(idx)))
    pass2 = _join_pass2_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, halo=False,
        interpret=interp,
    )
    table_dev = jnp.asarray(table.reshape(num * per2, 3))
    if volume is not None:
        _acc_volume(volume, pass2, table_dev, xp, replicated=xp.nbytes)
    ids, counts = pass2(table_dev, xp)  # (num * per2, bp, bp), (.., 1, bp)
    # shards hold contiguous ranges of the global schedule, so shard
    # order IS global order
    return pairs_from_masks(
        ids, counts, np.concatenate(rows), tri[tot > 0], P_total, bp, perm
    )


def _join_halo(
    x, xp, eps, *, mesh, axis, num, bp, D, pt, n_valid, tri, sorted_keys,
    interp, volume, perm
):
    # uniform resident layout: every shard owns ptl tiles (tail pure pad;
    # pad tiles never appear in the schedule, so n_valid is untouched)
    ptl = -(-pt // num)
    ptg = ptl * num
    xs = (
        jnp.pad(xp, ((0, ptg * bp - xp.shape[0]), (0, 0)))
        if ptg != pt else xp
    )
    reach = _tile_reach(np.asarray(x), pt, bp, eps, sorted_keys)
    pruned = tri[reach[tri[:, 0], tri[:, 1]]]  # global FGF order kept
    if len(pruned) == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    row_ids, plan, send_tables, slots, n_buf = _halo_plan(pruned, ptl, num)
    per_h = max(1, max(len(r) for r in row_ids))
    sched = np.zeros((num * per_h, 4), np.int32)
    for s in range(num):
        for r, g in enumerate(row_ids[s]):
            ti, tj = int(pruned[g, 0]), int(pruned[g, 1])
            js = tj - s * ptl if tj // ptl == s else slots[s][tj]
            sched[s * per_h + r] = (ti - s * ptl, js, ti, tj)

    pass1 = _halo_pass1_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, plan=plan,
        interpret=interp,
    )
    args1 = (jnp.asarray(sched), xs, *(jnp.asarray(t) for t in send_tables))
    if volume is not None:
        _acc_volume(volume, pass1, *args1)
    hits, buf = pass1(*args1)
    rows_tot = np.asarray(jnp.sum(hits, axis=1)).astype(np.int64)
    tot = np.zeros(len(pruned), np.int64)
    for s in range(num):
        k = len(row_ids[s])
        tot[row_ids[s]] = rows_tot[s * per_h : s * per_h + k]
    count("simjoin.tile_pairs", len(pruned))
    count("simjoin.tiles_live", int(np.count_nonzero(tot)))
    P_total = int(tot.sum())
    if P_total == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    check_pair_offsets(P_total, bp)
    # pass 2: each shard packs the tiles of its non-empty rows (slot +
    # global columns), padded to a uniform per-shard row count
    nz_rows = [r[tot[r] > 0] for r in row_ids]
    per2 = max(1, max(len(r) for r in nz_rows))
    table = np.zeros((num, per2, 5), np.int32)
    pos = np.zeros(len(pruned), np.int64)  # row -> position in pass 2
    for sh in range(num):
        local = np.searchsorted(row_ids[sh], nz_rows[sh])
        table[sh, : len(local), :4] = sched[sh * per_h + local]
        table[sh, : len(local), 4] = 1
        pos[nz_rows[sh]] = sh * per2 + np.arange(len(local))
    pass2 = _join_pass2_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, halo=True,
        interpret=interp,
    )
    table_dev = jnp.asarray(table.reshape(num * per2, 5))
    if volume is not None:
        _acc_volume(volume, pass2, table_dev, buf)
    ids, counts = pass2(table_dev, buf)  # (num * per2, bp, bp), (.., 1, bp)
    # read the shards' rows in the GLOBAL pruned-row order —
    # which equals the full triangle order because pruned rows are
    # provably pair-free — so the result is array-equal to single-core
    nz = tot > 0
    return pairs_from_masks(ids, counts, pos[nz], pruned[nz], P_total, bp, perm)


# ---------------------------------------------------------------------------
# Collective-volume accounting (bench rows; see launch.collective_volume)
# ---------------------------------------------------------------------------

def _acc_volume(vol: dict, fn, *args, replicated: int = 0) -> None:
    v = collective_volume(fn, *args, replicated_bytes=replicated)
    vol["bytes_per_shard"] = vol.get("bytes_per_shard", 0) + v["bytes_per_shard"]
    vol["replicated_bytes"] = (
        vol.get("replicated_bytes", 0) + v["replicated_bytes"]
    )
    counts = vol.setdefault("counts", {})
    for k, n in v["counts"].items():
        counts[k] = counts.get(k, 0) + n
    bts = vol.setdefault("bytes", {})
    for k, n in v["bytes"].items():
        bts[k] = bts.get(k, 0) + n


def simjoin_sharded_volume(
    x: jax.Array,
    eps: float,
    *,
    mesh,
    curve: str = "hilbert",
    bp: int = 256,
    hilbert_order: bool = False,
    halo: bool = True,
    interpret: bool | None = None,
) -> dict:
    """Measured communication of one sharded ε-join call: executed
    collective counts, per-primitive bytes, replicated-operand bytes and
    their ``bytes_per_shard`` total.  Runs the join (pass-2 tables are
    data-dependent) and accounts both passes.  The replicated path's
    cost is its per-pass x broadcast; the halo path's is its boundary
    ``ppermute`` strips — the bench rows CI compares."""
    vol = {"bytes_per_shard": 0, "replicated_bytes": 0, "counts": {}, "bytes": {}}
    simjoin_pairs_sharded(
        x, eps, mesh=mesh, curve=curve, bp=bp, hilbert_order=hilbert_order,
        halo=halo, interpret=interpret, _volume=vol,
    )
    return vol
