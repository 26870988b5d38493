"""E4 — the paper's §7 applications: k-means, similarity join,
Floyd-Warshall, Cholesky.  Correctness vs oracles + the schedule-level
economies (jump-over step savings, operand reloads), plus the
``apps_fused`` rows: phase-fused single-``pallas_call`` FW/Cholesky vs
the per-k-block reference (dispatch count, cold trace+compile time,
warm wall-clock, bit-match)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operand_reloads, tile_schedule, triangle_schedule
from repro.kernels import ops, ref


def _timed(fn):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _timed_best(fn, reps=3):
    """Warm-up once, then best-of-``reps`` wall clock (interpret-mode
    timings jitter enough on shared CPU to make single shots noisy)."""
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def _cold_dispatches(jit_fn, *args, **kwargs):
    """(pallas_call count, cold trace+compile+run seconds) of one call.

    Clears the jit cache first, then counts ``pl.pallas_call`` invocations
    while the program traces — exactly the number of kernel launches the
    compiled program will issue per execution.
    """
    from repro.kernels.pallas_compat import PallasCallCounter

    jit_fn.clear_cache()
    with PallasCallCounter() as spy:
        t0 = time.perf_counter()
        jax.block_until_ready(jit_fn(*args, **kwargs))
        cold = time.perf_counter() - t0
    return spy.count, cold


def run() -> list[dict]:
    rng = np.random.default_rng(7)
    rows = []

    # --- k-means assignment ------------------------------------------------
    x = jnp.asarray(rng.normal(size=(2048, 32)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(256, 32)), jnp.float32)
    for curve in ("row", "fur"):
        (d2, assign), dt = _timed(
            lambda: ops.kmeans_assign(x, c, curve=curve, bp=256, bc=64,
                                      interpret=True)
        )
        ok = bool((assign == ref.kmeans_assign(x, c)[1]).all())
        sched = tile_schedule(curve, 8, 4)
        rows.append({
            "bench": "kmeans", "name": f"assign_{curve}",
            "value": round(dt * 1e3, 1),
            "derived": f"ms; correct={ok}; reloads="
                       f"{operand_reloads(sched,0)+operand_reloads(sched,1)}",
        })

    # --- similarity join ----------------------------------------------------
    xj = jnp.asarray(rng.normal(size=(1024, 8)) * 0.6, jnp.float32)
    (counts, dt) = _timed(
        lambda: ops.simjoin_counts(xj, eps=0.9, curve="hilbert", bp=128,
                                   interpret=True)
    )
    ok = bool((counts == ref.simjoin_counts(xj, 0.9)).all())
    pt = 1024 // 128
    tri = triangle_schedule("hilbert", pt, strict=False)
    rows.append({
        "bench": "simjoin", "name": "counts_hilbert_jumpover",
        "value": round(dt * 1e3, 1),
        "derived": f"ms; correct={ok}; steps={len(tri)} vs full={pt*pt} "
                   f"(saved {1-len(tri)/(pt*pt):.0%})",
    })

    # --- Floyd-Warshall ------------------------------------------------------
    n = 96
    w = rng.uniform(1, 10, size=(n, n)).astype(np.float32)
    d0 = np.where(rng.uniform(size=(n, n)) < 0.2, w, np.inf).astype(np.float32)
    np.fill_diagonal(d0, 0.0)
    for curve in ("row", "hilbert"):
        out, dt = _timed(
            lambda: ops.floyd_warshall(jnp.asarray(d0), b=32, curve=curve,
                                       interpret=True)
        )
        err = float(jnp.abs(out - ref.floyd_warshall(jnp.asarray(d0))).max())
        rows.append({
            "bench": "floyd_warshall", "name": f"fw_{curve}_n{n}",
            "value": round(dt * 1e3, 1),
            "derived": f"ms; max_err={err:.1e}",
        })

    # --- Cholesky -------------------------------------------------------------
    n = 128
    m = rng.normal(size=(n, n)).astype(np.float32)
    a = m @ m.T + n * np.eye(n, dtype=np.float32)
    for curve in ("row", "hilbert"):
        L, dt = _timed(
            lambda: ops.cholesky(jnp.asarray(a), b=32, curve=curve,
                                 interpret=True)
        )
        err = float(jnp.abs(L - ref.cholesky(jnp.asarray(a))).max())
        rows.append({
            "bench": "cholesky", "name": f"chol_{curve}_n{n}",
            "value": round(dt * 1e3, 1),
            "derived": f"ms; max_err={err:.1e}",
        })

    # --- phase-fused FW/Cholesky: 1 pallas_call vs 3-4 per k-block ---------
    from repro.kernels.cholesky import cholesky_blocked, cholesky_blocked_reference
    from repro.kernels.floyd_warshall import (
        floyd_warshall_blocked,
        floyd_warshall_blocked_reference,
    )
    from repro.kernels.matmul import tile_update_swizzled

    n, b = 128, 16  # nt = 8
    w = rng.uniform(1, 10, size=(n, n)).astype(np.float32)
    dfw = np.where(rng.uniform(size=(n, n)) < 0.2, w, np.inf).astype(np.float32)
    np.fill_diagonal(dfw, 0.0)
    m = rng.normal(size=(n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)

    cases = [
        ("fw", jnp.asarray(dfw), floyd_warshall_blocked,
         floyd_warshall_blocked_reference, ()),
        ("chol", jnp.asarray(spd), cholesky_blocked,
         cholesky_blocked_reference, (tile_update_swizzled,)),
    ]
    for name, mat, fused_fn, ref_fn, extra_caches in cases:
        kw = dict(b=b, curve="hilbert", interpret=True)
        nd_fused, cold_fused = _cold_dispatches(fused_fn, mat, **kw)
        for f in extra_caches:  # nested jit caches would hide their calls
            f.clear_cache()
        nd_ref, cold_ref = _cold_dispatches(ref_fn, mat, **kw)
        out_f, warm_fused = _timed_best(lambda: fused_fn(mat, **kw))
        out_r, warm_ref = _timed_best(lambda: ref_fn(mat, **kw))
        bit = bool((np.asarray(out_f) == np.asarray(out_r)).all())
        rows.append({
            "bench": "apps_fused", "name": f"{name}_hilbert_nt{n // b}",
            "value": round(warm_fused * 1e3, 1),
            "derived": f"ms warm (ref {warm_ref * 1e3:.1f}); dispatches "
                       f"{nd_fused} vs {nd_ref}; cold {cold_fused:.2f}s vs "
                       f"{cold_ref:.2f}s; bit_identical={bit}",
        })

    # --- fused Lloyd k-means: 1 pallas_call per iteration under scan -------
    from repro.kernels.kmeans import (
        kmeans_assign_swizzled,
        kmeans_lloyd_fused,
        kmeans_update_swizzled,
    )
    from repro.kernels.pallas_compat import PallasCallCounter

    xk = jnp.asarray(rng.normal(size=(1024, 16)), jnp.float32)
    km_kw = dict(iters=3, curve="hilbert", bp=128, bc=16, interpret=True)
    kmeans_lloyd_fused.clear_cache()
    with PallasCallCounter() as spy:
        t0 = time.perf_counter()
        jax.block_until_ready(ops.kmeans_lloyd(xk, 64, fused=True, **km_kw)[0])
        cold_f = time.perf_counter() - t0
    nd_f = spy.count
    kmeans_assign_swizzled.clear_cache()
    kmeans_update_swizzled.clear_cache()
    with PallasCallCounter() as spy:
        ops.kmeans_lloyd(xk, 64, fused=False, **km_kw)
    nd_r = spy.count
    (cf, af), warm_f = _timed_best(
        lambda: ops.kmeans_lloyd(xk, 64, fused=True, **km_kw))
    (cr, ar), warm_r = _timed_best(
        lambda: ops.kmeans_lloyd(xk, 64, fused=False, **km_kw))
    bit = bool(
        (np.asarray(cf) == np.asarray(cr)).all()
        and (np.asarray(af) == np.asarray(ar)).all()
    )
    rows.append({
        "bench": "apps_fused", "name": "kmeans_hilbert_lloyd3",
        "value": round(warm_f * 1e3, 1),
        "derived": f"ms warm (ref {warm_r * 1e3:.1f}); traced pallas_calls "
                   f"{nd_f} (whole scanned loop) vs {nd_r}/iter; cold "
                   f"{cold_f:.2f}s; bit_identical={bit}",
    })

    # --- ε-join pair emission: two-pass count → prefix-sum → emit ----------
    from repro.kernels.simjoin import (
        simjoin_emit_swizzled,
        simjoin_totals,
    )

    xp = jnp.asarray(rng.normal(size=(768, 6)) * 0.6, jnp.float32)
    simjoin_totals.clear_cache()
    simjoin_emit_swizzled.clear_cache()
    with PallasCallCounter() as spy:
        ops.simjoin_pairs(xp, eps=0.8, curve="hilbert", bp=128, interpret=True)
    nd_p = spy.count
    pairs, warm_p = _timed_best(
        lambda: ops.simjoin_pairs(xp, eps=0.8, curve="hilbert", bp=128,
                                  interpret=True))
    got = np.asarray(pairs)
    got = got[np.lexsort((got[:, 1], got[:, 0]))]
    bit = bool(np.array_equal(got, ref.simjoin_pairs(xp, 0.8)))
    rows.append({
        "bench": "apps_fused", "name": "simjoin_hilbert_pairs",
        "value": round(warm_p * 1e3, 1),
        "derived": f"ms warm; {len(got)} pairs; dispatches {nd_p} "
                   f"(count+emit); bit_identical={bit}",
    })

    # --- sharded apps: curve-range shard_map over simulated devices --------
    # rows appear for every mesh size the process can simulate (CI's
    # sharded job and the committed BENCH_curves.json run under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 → 1/2/8).
    from repro.kernels.sharded import (
        kmeans_sharded_collectives,
        kmeans_sharded_volume,
        simjoin_pairs_sharded,
        simjoin_sharded_volume,
    )
    from repro.launch.mesh import make_app_mesh

    sizes = [s for s in (1, 2, 8) if s <= len(jax.devices())]
    xs = jnp.asarray(rng.normal(size=(512, 8)), jnp.float32)
    skm_kw = dict(iters=2, curve="hilbert", bp=64, bc=8, interpret=True)
    (c1, a1), warm_1 = _timed_best(
        lambda: ops.kmeans_lloyd(xs, 16, fused=True, **skm_kw))
    for s in sizes:
        mesh = make_app_mesh(s)
        (c2, a2), warm_s = _timed_best(
            lambda: ops.kmeans_lloyd(xs, 16, mesh=mesh, **skm_kw))
        bit = bool(
            (np.asarray(c1) == np.asarray(c2)).all()
            and (np.asarray(a1) == np.asarray(a2)).all()
        )
        coll = kmeans_sharded_collectives(xs, 16, mesh=mesh, **skm_kw)
        coll_s = "+".join(f"{v}x{k}" for k, v in sorted(coll.items()))
        vol_e = kmeans_sharded_volume(xs, 16, mesh=mesh, **skm_kw)
        rows.append({
            "bench": "apps_sharded", "name": f"kmeans_mesh{s}",
            "value": round(warm_s * 1e3, 1),
            "bytes_per_shard": int(vol_e["bytes_per_shard"]),
            "derived": f"ms warm (single-core {warm_1 * 1e3:.1f}); "
                       f"collectives/iter {coll_s}; bit_identical={bit}",
        })
        # hierarchical tree reduction: deterministic fold order (same
        # bits every run), allclose to single-core, fewer bytes
        (c3, _a3), warm_t = _timed_best(
            lambda: ops.kmeans_lloyd(xs, 16, mesh=mesh, shard_reduce="tree",
                                     **skm_kw))
        vol_t = kmeans_sharded_volume(xs, 16, mesh=mesh, reduce="tree",
                                      **skm_kw)
        close = bool(np.allclose(np.asarray(c1), np.asarray(c3),
                                 rtol=1e-5, atol=1e-5))
        rows.append({
            "bench": "apps_sharded", "name": f"kmeans_mesh{s}_tree",
            "value": round(warm_t * 1e3, 1),
            "bytes_per_shard": int(vol_t["bytes_per_shard"]),
            "derived": f"ms warm; tree-reduce bytes/shard "
                       f"{vol_t['bytes_per_shard']} vs exact "
                       f"{vol_e['bytes_per_shard']}; allclose={close}",
        })

    # ε-join: the halo join against one core, same pairs.  Replicating
    # the points instead would move the point buffer to every shard once
    # per pass; bytes_per_shard and point_bytes are top-level keys — the
    # CI bench smoke gates on halo bytes under the point buffer's.
    xjs = jnp.asarray(rng.normal(size=(384, 4)) * 0.6, jnp.float32)
    sj_kw = dict(bp=64, hilbert_order=True, interpret=True)
    pj1, warm_j1 = _timed_best(
        lambda: ops.simjoin_pairs(xjs, eps=0.8, **sj_kw))
    for s in sizes:
        mesh = make_app_mesh(s)
        pj2, warm_js = _timed_best(
            lambda: simjoin_pairs_sharded(xjs, 0.8, mesh=mesh, **sj_kw))
        vol = simjoin_sharded_volume(xjs, 0.8, mesh=mesh, **sj_kw)
        bit = bool(np.array_equal(np.asarray(pj1), np.asarray(pj2)))
        coll_s = "+".join(
            f"{v}x{k}" for k, v in sorted(vol["counts"].items())
        ) or "0"
        rows.append({
            "bench": "apps_sharded", "name": f"simjoin_mesh{s}_halo",
            "value": round(warm_js * 1e3, 1),
            "bytes_per_shard": int(vol["bytes_per_shard"]),
            "point_bytes": int(xjs.nbytes),
            "derived": f"ms warm (single-core {warm_j1 * 1e3:.1f}); "
                       f"{len(np.asarray(pj2))} pairs; collectives "
                       f"{coll_s}; replicated {2 * xjs.nbytes} "
                       f"(point buffer once per pass); bit_identical={bit}",
        })

    # halo bytes scale with the BOUNDARY area: 4x the points in 4x the
    # area (fixed density) must grow halo traffic sublinearly while full
    # replication grows 4x
    if sizes and max(sizes) >= 2:
        mesh = make_app_mesh(max(sizes))
        rngu = np.random.default_rng(11)
        scaling = {}
        for N, side in ((512, 1.0), (2048, 2.0)):
            xh = jnp.asarray(rngu.uniform(size=(N, 2)) * side, jnp.float32)
            kwv = dict(mesh=mesh, bp=64, hilbert_order=True, interpret=True)
            vh = simjoin_sharded_volume(xh, 0.05, **kwv)
            scaling[N] = vh["bytes_per_shard"]
            rows.append({
                "bench": "apps_sharded", "name": f"simjoin_halo_scaling_N{N}",
                "value": int(vh["bytes_per_shard"]),
                "bytes_per_shard": int(vh["bytes_per_shard"]),
                "point_bytes": int(xh.nbytes),
                "derived": f"halo bytes/shard (replicated {2 * xh.nbytes}: "
                           f"the point buffer once per pass); uniform "
                           f"density, side={side}, mesh{max(sizes)}",
            })
        ratio_h = scaling[2048] / scaling[512]
        rows.append({
            "bench": "apps_sharded", "name": "simjoin_halo_scaling_ratio",
            "value": round(ratio_h, 2),
            "derived": "halo bytes growth for 4x N at fixed density "
                       "(replication grows 4x); sublinear boundary scaling",
        })
    return rows
