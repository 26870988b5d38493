"""Proof that the system's served paths run on a TPU chip.

    python chip_smoke.py [--seed N]      # one chip: apps, stream, serve
    python chip_smoke.py --chips 4       # four chips: the sharded apps

One process drives every phase through the entry points a user calls
(``repro.kernels.ops``, the streaming services, ``ServeEngine``) with
compiled Pallas kernels (never the interpreter), checks each result
against a plain reference on the same chip, and prints one JSON line
per phase.  The last line of standard output is
``{"ok": true, "device": {...}}`` and nothing else.  Without a TPU —
or without the rest of the repository beside this file — it exits
non-zero and prints no result.

Phases (one chip):

* ``apps`` — the five §7 apps at working sets above VMEM: Floyd-Warshall
  and Cholesky at n=4096, Lloyd k-means at N=2^20, D=16, K=256 (3
  iterations), matmul at 4096² bf16 (2-D and 3-D schedules), the ε-join
  at N=16384, D=8 with ~10 neighbours per point; plus fused == reference
  bit-identity for FW and Cholesky at n=1024.  Each app must have taken
  its fused path: the VMEM-budget gate warns when it routes an app to
  the reference path, and the phase records and fails on that.
* ``stream`` — ``StreamKMeans`` and ``StreamSimJoin`` over 8 ticks of
  4096-point cohorts, each ending equal to the one-shot batch result.
* ``serve`` — tinyllama-1.1b at full width (bf16 weights from
  ``--seed``) through the paged, flash, compiled-prefill,
  prefix-sharing ``ServeEngine``: 8 requests of 512-1024 prompt tokens
  (half share a 256-token prefix), 32 new tokens each; the first decode
  step's logits are compared with the ``attn_impl="xla"`` engine.

``--chips 4`` runs only ``ops.kmeans_lloyd(mesh=)`` and the halo
``ops.simjoin_pairs(mesh=)`` on a 4-device mesh, each compared with the
single-chip result (bit-identical / array-equal).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (XLA and Mosaic, or a
    persistent-cache read in its place), from its own monitoring event,
    read per phase.  Tracing is left out: its events nest, one per
    inner jit, and would count twice."""

    def __init__(self):
        from jax import monitoring

        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.total += secs

    def lap(self) -> float:
        out, self.total = self.total, 0.0
        return round(out, 2)


def check(checks: list, name: str, value, limit, ok: bool) -> None:
    checks.append({"check": name, "value": value, "limit": limit, "ok": bool(ok)})


def run_app(paths: dict, app: str, fn):
    """``fn()``, recording in ``paths[app]`` whether the app ran its
    fused kernel or was routed to the reference path (the VMEM-budget
    gate warns when it does that)."""
    import warnings

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", RuntimeWarning)
        out = fn()
    routed = any("VMEM" in str(w.message) for w in caught
                 if issubclass(w.category, RuntimeWarning))
    paths[app] = "reference" if routed else "fused"
    return out


# ---------------------------------------------------------------------------
# data, made on the device from the seed
# ---------------------------------------------------------------------------

def digraph(key, n: int):
    """Integer-weighted digraph (sums stay exact in f32), +inf non-edges."""
    import jax
    import jax.numpy as jnp

    kw, ke = jax.random.split(key)
    w = jax.random.randint(kw, (n, n), 1, 10).astype(jnp.float32)
    d = jnp.where(jax.random.uniform(ke, (n, n)) < 8.0 / n, w, jnp.inf)
    return jnp.where(jnp.eye(n, dtype=bool), 0.0, d)


def spd(key, n: int):
    import jax
    import jax.numpy as jnp

    m = jax.random.normal(key, (n, n), jnp.float32)
    a = jnp.dot(m, m.T, precision="highest") / n + jnp.eye(n, dtype=jnp.float32)
    return (a + a.T) / 2


def blobs(key, n: int, d: int, k: int, scale: float = 10.0, sigma: float = 0.3):
    import jax
    import jax.numpy as jnp

    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (k, d), jnp.float32) * scale
    which = jax.random.randint(ka, (n,), 0, k)
    return centers[which] + sigma * jax.random.normal(kn, (n, d), jnp.float32)


def int_points(key, n: int, d: int, side: int = 256):
    """Integer coordinates: every squared distance is an exact f32."""
    import jax
    import jax.numpy as jnp

    return jax.random.randint(key, (n, d), 0, side).astype(jnp.float32)


# ---------------------------------------------------------------------------
# plain references on the same chip
# ---------------------------------------------------------------------------

def ref_lloyd(x, c0, iters: int, chunk: int = 1 << 16):
    """Plain Lloyd: f32 distances at full matmul precision, segment sums."""
    import jax
    import jax.numpy as jnp

    k = c0.shape[0]

    @jax.jit
    def assign(x, c):
        def one(xc):
            d2 = (
                jnp.sum(xc * xc, axis=1, keepdims=True)
                - 2.0 * jnp.dot(xc, c.T, precision="highest")
                + jnp.sum(c * c, axis=1)[None, :]
            )
            return jnp.argmin(d2, axis=1).astype(jnp.int32)

        return jax.lax.map(one, x.reshape(-1, chunk, x.shape[1])).reshape(-1)

    @jax.jit
    def update(x, a, c):
        s = jax.ops.segment_sum(x, a, num_segments=k)
        n = jax.ops.segment_sum(jnp.ones_like(a, jnp.float32), a, num_segments=k)
        return jnp.where(n[:, None] > 0, s / jnp.maximum(n, 1.0)[:, None], c)

    c, a = c0, None
    for _ in range(iters):
        a = assign(x, c)
        c = update(x, a, c)
    return c, a


def ref_pairs(x, eps: float, chunk: int = 2048):
    """Dense ε-join, row chunk by row chunk: int[P, 2] (i > j), sorted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = x.shape[0]
    xn = jnp.sum(x * x, axis=1)

    @jax.jit
    def hits(lo):
        xi = jax.lax.dynamic_slice_in_dim(x, lo, chunk)
        d2 = (
            jax.lax.dynamic_slice_in_dim(xn, lo, chunk)[:, None]
            - 2.0 * jnp.dot(xi, x.T, precision="highest")
            + xn[None, :]
        )
        i = lo + jnp.arange(chunk)[:, None]
        return (d2 <= eps * eps) & (jnp.arange(n)[None, :] < i)

    out = []
    for lo in range(0, n, chunk):
        i, j = np.nonzero(np.asarray(hits(lo)))
        out.append(np.stack([i + lo, j], axis=1))
    p = np.concatenate(out).astype(np.int64)
    return p[np.lexsort((p[:, 1], p[:, 0]))]


def sorted_pairs(p):
    import numpy as np

    p = np.asarray(p, dtype=np.int64).reshape(-1, 2)
    return p[np.lexsort((p[:, 1], p[:, 0]))]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_apps(key, *, interpret=False, n=4096, n_ref=1024, lloyd=(2**20, 16, 256),
               mm=4096, join=(16384, 8), eps2=9000.5):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    checks: list = []
    ks = jax.random.split(key, 6)
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))  # noqa: E731

    paths = {}  # the path each app took on its checked call

    d = digraph(ks[0], n)
    got = run_app(paths, "floyd_warshall",
                  lambda: ops.floyd_warshall(d, interpret=interpret))
    want = ref.floyd_warshall(d)
    check(checks, "floyd_warshall_mismatches", int(jnp.sum(got != want)), 0,
          bool(jnp.all(got == want)))

    a = spd(ks[1], n)
    L = run_app(paths, "cholesky", lambda: ops.cholesky(a, interpret=interpret))
    res = float(
        jnp.max(jnp.abs(jnp.dot(L, L.T, precision="highest") - a)) / jnp.max(jnp.abs(a))
    )
    check(checks, "cholesky_rel_residual", res, 1e-5, res <= 1e-5)
    err = rel(L, ref.cholesky(a))
    check(checks, "cholesky_rel_err_vs_ref", err, 1e-3, err <= 1e-3)

    # fused == per-k-block reference, bit for bit (the reference paths
    # visit every block once per call; the fused ones revisit via DMA)
    dr = digraph(ks[2], n_ref)
    same = jnp.all(ops.floyd_warshall(dr, interpret=interpret)
                   == ops.floyd_warshall(dr, fused=False, interpret=interpret))
    check(checks, "floyd_warshall_fused_eq_reference", bool(same), True, same)
    ar = spd(ks[2], n_ref)
    same = jnp.all(ops.cholesky(ar, interpret=interpret)
                   == ops.cholesky(ar, fused=False, interpret=interpret))
    check(checks, "cholesky_fused_eq_reference", bool(same), True, same)

    N, D, K = lloyd
    x = blobs(ks[3], N, D, K)
    from repro.kernels.kmeans import kmeans_init

    c0 = kmeans_init(x, K, 0)
    chunk = min(1 << 16, N)
    # one iteration from the shared c0 isolates the numerics: points the
    # kernel assigns differently from the reference must be ties (f32
    # rounding of ||c||^2 - 2 c.x at ||x||^2 ~ 500), and the update must
    # be the mean of the kernel's own assignment
    c1, a1 = run_app(paths, "kmeans_lloyd", lambda: ops.kmeans_lloyd(
        x, K, iters=1, seed=0, interpret=interpret))
    _, a_ref = ref_lloyd(x, c0, 1, chunk=chunk)
    diff = np.nonzero(np.asarray(a1 != a_ref))[0]
    xd, c0h = np.asarray(x)[diff].astype(np.float64), np.asarray(c0, np.float64)
    gap = np.abs(((xd - c0h[np.asarray(a1)[diff]]) ** 2).sum(1)
                 - ((xd - c0h[np.asarray(a_ref)[diff]]) ** 2).sum(1))
    worst = float(gap.max()) if len(diff) else 0.0
    check(checks, "kmeans_iter1_flips_are_ties", {"flips": int(len(diff)),
          "max_sqdist_gap": worst}, 1e-3, worst <= 1e-3)
    seg = jax.ops.segment_sum(x, a1, num_segments=K)
    cnt = jax.ops.segment_sum(jnp.ones((N,), jnp.float32), a1, num_segments=K)
    c1_want = jnp.where(cnt[:, None] > 0, seg / jnp.maximum(cnt, 1.0)[:, None], c0)
    uerr = float(jnp.max(jnp.abs(c1 - c1_want)))
    check(checks, "kmeans_iter1_update_max_abs_err", uerr, 1e-4, uerr <= 1e-4)
    # three iterations: trajectories may part at the ties above
    c, assign = ops.kmeans_lloyd(x, K, iters=3, seed=0, interpret=interpret)
    c_ref, a_ref = ref_lloyd(x, c0, 3, chunk=chunk)
    agree = float(jnp.mean((assign == a_ref).astype(jnp.float32)))
    check(checks, "kmeans_iter3_assign_agreement", agree, 0.999, agree >= 0.999)
    cerr = float(jnp.max(jnp.abs(c - c_ref)))
    check(checks, "kmeans_iter3_centroid_max_abs_err", cerr, 1e-2, cerr <= 1e-2)

    km, kn = jax.random.split(ks[4])
    am = jax.random.normal(km, (mm, mm), jnp.float32).astype(jnp.bfloat16)
    bm = jax.random.normal(kn, (mm, mm), jnp.float32).astype(jnp.bfloat16)
    want = ref.matmul(am, bm).astype(jnp.float32)
    for nd in (2, 3):
        got = run_app(paths, f"matmul{nd}d", lambda: ops.matmul(  # noqa: B023
            am, bm, schedule_ndim=nd, interpret=interpret))
        err = rel(got.astype(jnp.float32), want)
        check(checks, f"matmul{nd}d_rel_err", err, 1e-2, err <= 1e-2)

    Nj, Dj = join
    xj = int_points(ks[5], Nj, Dj)
    eps = float(np.sqrt(eps2))
    pairs = sorted_pairs(run_app(paths, "simjoin_pairs", lambda: ops.simjoin_pairs(
        xj, eps, hilbert_order=True, interpret=interpret)))
    want = ref_pairs(xj, eps, chunk=min(2048, Nj))
    check(checks, "simjoin_pairs", int(len(pairs)), int(len(want)),
          np.array_equal(pairs, want))
    check(checks, "simjoin_neighbours_per_point", 2 * len(want) / Nj,
          "~10", len(want) > 0)
    check(checks, "fused_paths", paths, "fused",
          all(v == "fused" for v in paths.values()))
    return checks


def phase_stream(key, *, interpret=False, cohort=4096, ticks=8, k=64):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.serve import StreamKMeans, StreamSimJoin

    checks: list = []
    ka, kb = jax.random.split(key)
    pts = np.asarray(blobs(ka, cohort * ticks, 8, k, scale=1.0, sigma=0.03))
    chunks = np.split(pts, ticks)
    bp = min(256, cohort)
    paths: dict = {}

    def online():
        svc = StreamKMeans(k, bp=bp, interpret=interpret)
        for ch in chunks:
            svc.insert(ch)
            svc.tick()
        return svc

    def batch_like():
        # the bit-identity contract is for a fully inserted set: admit
        # all, then one Lloyd iteration per tick
        full = StreamKMeans(k, bp=bp, interpret=interpret)
        for ch in chunks:
            full.insert(ch)
        for _ in range(ticks):
            full.tick()
        return full

    svc = run_app(paths, "StreamKMeans", online)
    full = run_app(paths, "StreamKMeans_full", batch_like)
    c_b, a_b = ops.kmeans_lloyd(jnp.asarray(full.points()), k, iters=ticks,
                                bp=bp, interpret=interpret)
    same = bool(np.array_equal(full.centroids(), np.asarray(c_b))
                and np.array_equal(full.assignment(), np.asarray(a_b)))
    check(checks, "stream_kmeans_eq_batch", same, True, same)
    check(checks, "stream_kmeans_finite", bool(np.isfinite(svc.centroids()).all()),
          True, np.isfinite(svc.centroids()).all())

    data = np.asarray(jax.random.uniform(kb, (cohort * ticks, 3), jnp.float32))
    eps = float((10.0 / (len(data) * 4.18879)) ** (1 / 3))  # ~10 neighbours
    join = StreamSimJoin(eps, bp=bp, bounds=(data.min(0), data.max(0)),
                         interpret=interpret)
    for ch in np.split(data, ticks):
        join.insert(ch)
        join.tick()
    want = sorted_pairs(ops.simjoin_pairs(jnp.asarray(join.points_by_id()), eps,
                                          interpret=interpret))
    got = sorted_pairs(join.pairs())
    check(checks, "stream_simjoin_eq_batch", int(len(got)), int(len(want)),
          np.array_equal(got, want))
    check(checks, "fused_paths", paths, "fused",
          all(v == "fused" for v in paths.values()))
    return checks


def phase_serve(key, *, cfg=None, slots=8, max_len=2048, prefix=256,
                lengths=((512, 512, 500, 480), (1024, 900, 700, 600)),
                new=32, logit_tol=5e-2):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import ServeEngine

    checks: list = []
    cfg = cfg or get_config("tinyllama-1.1b")
    params = init_params(key, cfg)
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    shared = rng.integers(0, cfg.vocab_size, prefix).tolist()
    cohorts = []
    for lens in lengths:
        reqs = []
        for i, n in enumerate(lens):
            head = shared if i < len(lens) // 2 else []
            reqs.append(head + rng.integers(0, cfg.vocab_size, n - len(head)).tolist())
        cohorts.append(reqs)
    kw = dict(num_slots=slots, max_len=max_len, paged=True, prefill="compiled",
              prefix_sharing=True)

    eng = ServeEngine(cfg, params, attn_impl="flash", **kw)
    live = [eng.submit(p, max_new=new) for p in cohorts[0]]
    eng.step()  # admission (compiled prefill) + the first decode step
    first_flash = eng.last_logits
    xla = ServeEngine(cfg, params, attn_impl="xla", **kw)
    for p in cohorts[0]:
        xla.submit(p, max_new=new)
    xla.step()
    rows = np.arange(len(cohorts[0]))
    err = float(np.max(np.abs(first_flash[rows] - xla.last_logits[rows]))
                / np.max(np.abs(xla.last_logits[rows])))
    del xla
    check(checks, "first_decode_logits_rel_err_vs_xla", err, logit_tol,
          err <= logit_tol)
    finite = bool(np.isfinite(first_flash).all())
    for p in cohorts[1]:
        live.append(eng.submit(p, max_new=new))
    t0 = time.perf_counter()
    while eng._queue or eng.active.any():
        eng.step()
        finite &= bool(np.isfinite(eng.last_logits).all())
    toks = [len(r.out) for r in live]
    check(checks, "tokens_per_request", toks, new, all(t == new for t in toks))
    check(checks, "logits_finite", finite, True, finite)
    shared_pages = int(eng.kv_pages.stat_shared)
    check(checks, "prefix_pages_shared", shared_pages, ">0", shared_pages > 0)
    return checks, time.perf_counter() - t0


def phase_chips4(key, *, interpret=False, lloyd=(2**20, 16, 256), join=(16384, 8),
                 eps2=9000.5, num=4):
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.launch.mesh import make_app_mesh

    checks: list = []
    mesh = make_app_mesh(num)
    ka, kb = jax.random.split(key)
    N, D, K = lloyd
    x = blobs(ka, N, D, K)
    c1, a1 = ops.kmeans_lloyd(x, K, iters=3, interpret=interpret)
    c4, a4 = ops.kmeans_lloyd(x, K, iters=3, mesh=mesh, interpret=interpret)
    same = bool(np.array_equal(np.asarray(c1), np.asarray(c4))
                and np.array_equal(np.asarray(a1), np.asarray(a4)))
    check(checks, "kmeans_mesh_bit_identical", same, True, same)
    Nj, Dj = join
    xj = int_points(kb, Nj, Dj)
    eps = float(np.sqrt(eps2))
    p1 = np.asarray(ops.simjoin_pairs(xj, eps, hilbert_order=True, interpret=interpret))
    p4 = np.asarray(ops.simjoin_pairs(xj, eps, hilbert_order=True, mesh=mesh,
                                      interpret=interpret))
    check(checks, "simjoin_halo_array_equal", int(len(p4)), int(len(p1)),
          np.array_equal(p1, p4))
    return checks


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    try:
        import jax

        from repro.kernels.launch import resolve_interpret
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program beside this file: {e}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}; "
              "there is no CPU fallback", file=sys.stderr)
        return 1
    if resolve_interpret(None):
        print("chip_smoke: kernels would run in interpret mode", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    emit({"phase": "device", **device, "compile_cache": cache})

    clock = CompileClock()
    key = jax.random.PRNGKey(args.seed)
    ok = True
    if args.chips == 4:
        phases = [("chips4", lambda: phase_chips4(key, num=4))]
    else:
        k1, k2, k3 = jax.random.split(key, 3)
        phases = [
            ("apps", lambda: phase_apps(k1)),
            ("stream", lambda: phase_stream(k2)),
            ("serve", lambda: phase_serve(k3)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            out = run()
            extra = {}
            if isinstance(out, tuple):
                out, serve_s = out
                extra["serve_loop_s"] = round(serve_s, 2)
            checks = out
            passed = all(c["ok"] for c in checks)
        except Exception as e:  # a phase that raises has failed
            checks, passed, extra = [{"check": "raised", "value": repr(e)[:2000],
                                      "ok": False}], False, {}
        ok &= passed
        emit({"phase": name, "ok": passed, "wall_s": round(time.perf_counter() - t0, 2),
              "compile_s": clock.lap(), **extra, "checks": checks})
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
