"""shard_map scale-out of the data-mining apps (curve-range partitioned).

Shards hold contiguous runs of curve-ordered work: for k-means runs of
(Hilbert-sorted) point tiles of the same schedule tables that drive the
fused single-core kernel, for the ε-join runs of point tiles, each
owning the kept tile pairs of its i tiles — so every shard works a
compact, low-surface region of the problem (the paper's locality
argument applied to the mesh instead of the cache).  The contract of
such a partition (disjoint, covering, contiguous in Hilbert order) is
:func:`repro.core.curve_partition`; the apps use its SPMD-uniform
specialisation — equal-length ranges, the tail padded with inert rows —
because ``shard_map`` traces ONE program for all shards and therefore
needs equal shapes.

**k-means** (:func:`kmeans_lloyd_sharded`): every device runs the
shard-local Lloyd-step program (phase-fused assign + per-tile update
partials, ONE pallas dispatch per iteration per shard) under
``shard_map`` with the iteration loop in ``lax.scan``.  Cross-shard
reduction is split by exactness class:

* counts are integer-valued f32, so a plain ``psum`` is EXACT under any
  reduction grouping;
* the f32 coordinate sums are NOT association-free, so ``reduce``
  selects an exactness class: ``"exact"`` (default) ``all_gather``\\ s
  the per-tile partials and folds them in the *single-core fused
  kernel's own accumulation order* (the phase-1 first-appearance order
  of the global schedule) — BIT-identical to single-core on any mesh
  size; ``"tree"`` folds locally then combines shards through a fixed
  recursive-doubling butterfly (deterministic association ⇒ bit-stable
  run to run, O(K·D·log S) bytes, allclose to single-core);
  ``"psum"`` leaves the association to the compiler (cheapest).

The wrapper reproduces the single-core wrapper's padding and tiling
decisions bit-for-bit (same ``bp`` clamp, same zero-pad + index-mask
rule, same ``kmeans_init`` centroids).

**ε-join** (:func:`simjoin_pairs_sharded`): the halo join.  Its point
order, ``bp`` clamp, padding and device-pruned tile-pair schedule are
the single-chip join's own, from :func:`repro.kernels.simjoin.
simjoin_inputs`; this module adds only what the mesh needs.  x is
point-sharded ``P(axis, None)`` in whole tiles, each kept row goes to
the owner of its i tile, and the foreign j tiles a shard still needs
are ``ppermute``\\ d in as boundary strips into a fixed-size halo
buffer, uniform across shards (:func:`_halo_plan`).  Pass 1 counts the
rows' hits with the ``simjoin_hits`` program of the single-chip join;
the host reads the totals (an exact-size join's inherent sync) and
keeps the non-empty rows; pass 2 reuses pass 1's buffer, so each strip
moves once, and one exact-size flatten reads the shards' packed rows in
the schedule's order — so the pairs are array-equal to the single-chip
join's by construction, which tests/test_apps_sharded.py asserts across
mesh sizes.  Collective bytes scale with the boundary area, not N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (
    curve_partition,
    kmeans_schedule,
    kmeans_schedule_device,
    register_schedule_cache,
)
from repro.core.tracing import count

from .kmeans import (
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
    simjoin_hits_program,
    simjoin_inputs,
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
    vol = collective_volume(fn, *args)
    vol["bytes_per_shard"] += args[1].nbytes  # the centroids, to every shard
    return vol


# ---------------------------------------------------------------------------
# ε-join: the halo join (point-sharded, boundary strips by ppermute)
# ---------------------------------------------------------------------------

@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _halo_pass1_fn(mesh, axis, *, eps, bp, D, n_valid, plan, interpret):
    """Point-sharded pass 1: neighbour-exchange the boundary strips the
    plan names, then count hits on resident+halo tiles.

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
        program = simjoin_hits_program(
            sched_l, eps=eps, bp=bp, D=D, n_valid=n_valid
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


@register_schedule_cache
@functools.lru_cache(maxsize=64)
def _join_pass2_fn(mesh, axis, *, eps, bp, D, n_valid, interpret):
    """Pass 2 on every shard: the packed hit rows and row counts of its
    live rows of the 5-column table, over the resident+halo buffer pass
    1 assembled."""

    def body(table_l, buf):
        program = simjoin_emit_program(
            table_l, eps=eps, bp=bp, D=D, n_valid=n_valid
        )
        return tuple(launch(program, buf, buf.T, interpret=interpret))

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def _halo_plan(pruned: np.ndarray, ptl: int, num: int):
    """Host-side exchange plan for a pruned schedule.

    Rows go to the shard owning their *i* tile; every foreign *j* tile is
    a lower tile (``j <= i`` in the triangle), so strips only flow up the
    ring.  Returns ``(row_ids, plan, send_tables, slots)``: per-shard
    row indices into ``pruned`` (global order preserved), the static
    ``(delta, m)`` topology, per-delta int32[num, m] sender-local tile
    tables, and int[num, num·ptl] maps of global tile -> the shard's
    buffer slot (-1: not in its buffer): its ``ptl`` resident tiles,
    then each delta's ``m`` received ones.
    """
    owner = pruned[:, 0] // ptl
    row_ids = [np.flatnonzero(owner == s) for s in range(num)]
    need = [np.unique(pruned[r, 1]) for r in row_ids]
    slots = np.full((num, num * ptl), -1, np.int64)
    for s in range(num):
        slots[s, s * ptl:(s + 1) * ptl] = np.arange(ptl)
    plan, send_tables = [], []
    base = ptl
    for delta in range(1, num):
        # what shard s receives from the shard delta below it
        recv = {s: need[s][need[s] // ptl == s - delta] for s in range(delta, num)}
        m = max((len(t) for t in recv.values()), default=0)
        if m == 0:
            continue
        tbl = np.zeros((num, m), np.int32)
        for s, t in recv.items():
            tbl[s - delta, : len(t)] = t - (s - delta) * ptl
            slots[s, t] = base + np.arange(len(t))
        plan.append((delta, m))
        send_tables.append(tbl)
        base += m
    return row_ids, tuple(plan), send_tables, slots


def simjoin_pairs_sharded(
    x: jax.Array,
    eps: float,
    *,
    mesh,
    curve: str = "hilbert",
    bp: int = 256,
    hilbert_order: bool = False,
    interpret: bool | None = None,
    _volume: dict | None = None,
) -> jax.Array:
    """The halo ε-join over a device mesh: int32[P, 2] pairs, i > j,
    array-equal to ``ops.simjoin_pairs`` on every mesh size.

    The point order, padding and device-pruned schedule are the
    single-chip join's (:func:`repro.kernels.simjoin.simjoin_inputs`).
    x is point-sharded (``P(axis, None)``, whole tiles), each kept row
    runs on the shard owning its *i* tile, and the only cross-device
    data motion is a ``ppermute`` of the boundary strips the rows name —
    a fixed-size halo buffer per shard, reused by pass 2.  Per-shard hit
    counts → the non-empty rows and the exact pair count on the host
    (the inherent host sync of an exact-size join) → per-shard packed
    hit rows of those rows → one flatten kernel in the schedule's order.
    """
    if x.shape[0] == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    eps = float(eps)
    xp, bp, n_valid, perm, sched, kept = simjoin_inputs(
        x, eps, curve=curve, bp=bp, hilbert_order=hilbert_order
    )
    count("simjoin.tile_pairs", kept)
    pruned = np.asarray(sched[:kept])
    axis, num = mesh_axis(mesh)
    interp = resolve_interpret(interpret)
    D = xp.shape[1]
    # uniform resident layout: every shard owns ptl tiles (tail pure pad;
    # pad tiles never appear in the schedule, so n_valid is untouched)
    ptl = -(-(xp.shape[0] // bp) // num)
    pn = ptl * num * bp - xp.shape[0]
    xs = jnp.pad(xp, ((0, pn), (0, 0))) if pn else xp
    row_ids, plan, send_tables, slots = _halo_plan(pruned, ptl, num)
    per_h = max(1, max(len(r) for r in row_ids))
    table1 = np.zeros((num, per_h, 4), np.int32)  # (i_slot, j_slot, i, j)
    for s, r in enumerate(row_ids):
        ti, tj = pruned[r, 0], pruned[r, 1]
        table1[s, : len(r)] = np.stack([slots[s, ti], slots[s, tj], ti, tj], 1)

    pass1 = _halo_pass1_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, plan=plan,
        interpret=interp,
    )
    args1 = (
        jnp.asarray(table1.reshape(-1, 4)), xs,
        *(jnp.asarray(t) for t in send_tables),
    )
    if _volume is not None:
        _acc_volume(_volume, pass1, *args1)
    hits, buf = pass1(*args1)
    rows_tot = np.asarray(jnp.sum(hits, axis=1)).astype(np.int64).reshape(num, per_h)
    tot = np.zeros(kept, np.int64)
    for s, r in enumerate(row_ids):
        tot[r] = rows_tot[s, : len(r)]
    count("simjoin.tiles_live", int(np.count_nonzero(tot)))
    P_total = int(tot.sum())
    if P_total == 0:
        return jnp.zeros((0, 2), dtype=jnp.int32)
    check_pair_offsets(P_total, bp)
    # pass 2: each shard packs the tiles of its non-empty rows, padded to
    # a uniform per-shard row count
    local = [np.flatnonzero(tot[r]) for r in row_ids]
    per2 = max(1, max(len(k) for k in local))
    table = np.zeros((num, per2, 5), np.int32)
    pos = np.zeros(kept, np.int64)  # row -> its place in pass 2's output
    for s, k in enumerate(local):
        table[s, : len(k), :4] = table1[s, k]
        table[s, : len(k), 4] = 1
        pos[row_ids[s][k]] = s * per2 + np.arange(len(k))
    pass2 = _join_pass2_fn(
        mesh, axis, eps=eps, bp=bp, D=D, n_valid=n_valid, interpret=interp,
    )
    table_dev = jnp.asarray(table.reshape(num * per2, 5))
    if _volume is not None:
        _acc_volume(_volume, pass2, table_dev, buf)
    ids, counts = pass2(table_dev, buf)  # (num * per2, bp, bp), (.., 1, bp)
    # read the shards' rows in the schedule's order: the single-chip
    # join's emission order
    nz = tot > 0
    return pairs_from_masks(ids, counts, pos[nz], pruned[nz], P_total, bp, perm)


# ---------------------------------------------------------------------------
# Collective-volume accounting (bench rows; see launch.collective_volume)
# ---------------------------------------------------------------------------

def _acc_volume(vol: dict, fn, *args) -> None:
    v = collective_volume(fn, *args)
    vol["bytes_per_shard"] = vol.get("bytes_per_shard", 0) + v["bytes_per_shard"]
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
    interpret: bool | None = None,
) -> dict:
    """Measured communication of one sharded ε-join call: executed
    collective counts, per-primitive bytes and their ``bytes_per_shard``
    total.  Runs the join (pass-2 tables are data-dependent) and accounts
    both passes: the boundary ``ppermute`` strips of pass 1 (pass 2
    reuses its buffer) — the bench rows CI compares with the point
    buffer's bytes, what replicating x would move per pass."""
    vol = {"bytes_per_shard": 0, "counts": {}, "bytes": {}}
    simjoin_pairs_sharded(
        x, eps, mesh=mesh, curve=curve, bp=bp, hilbert_order=hilbert_order,
        interpret=interpret, _volume=vol,
    )
    return vol
