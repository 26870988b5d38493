"""Production mesh construction (+ Hilbert ICI layout, beyond-paper).

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state): 16×16 ("data", "model") single-pod, or 2×16×16
("pod", "data", "model") across two pods.

Beyond-paper: ``hilbert_device_order`` re-orders the flat device list so
that walking the logical (data, model) grid follows physical-torus
locality — the same space-filling-curve argument the paper makes for
cache lines, applied to ICI hops.  On a (16,16) logical grid mapped to a
2-D torus, Hilbert ordering keeps logically-adjacent shards physically
adjacent at every scale; ``benchmarks/bench_mesh.py`` quantifies the hop
histogram against the default raster layout.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False, hilbert_layout: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if not hilbert_layout:
        # Auto axes: the models place arrays with sharding constraints,
        # which jax.make_mesh's default Explicit axes refuse
        auto = (jax.sharding.AxisType.Auto,) * len(axes)
        return jax.make_mesh(shape, axes, axis_types=auto)
    # Hilbert layout: permute devices so the logical grid walk is a
    # Hilbert walk over the physical (row-major) torus coordinates.
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices())
    per_pod = int(np.prod(shape[-2:]))
    n, m = shape[-2], shape[-1]
    perm = hilbert_grid_permutation(n, m)
    pods = len(devices) // per_pod if multi_pod else 1
    ordered = []
    for p in range(pods):
        pod = devices[p * per_pod : (p + 1) * per_pod]
        ordered.append(pod[perm].reshape(n, m))
    arr = np.stack(ordered) if multi_pod else ordered[0]
    return Mesh(arr, axes)


def hilbert_grid_permutation(n: int, m: int) -> np.ndarray:
    """perm[i*m + j] = physical device index for logical cell (i, j):
    logical raster position k gets the device at the k-th step of the
    FUR-Hilbert walk of the physical grid."""
    from repro.core import fur_path

    path = fur_path(n, m)  # physical coords in Hilbert order
    perm = np.empty(n * m, dtype=np.int64)
    # walk logical cells in hilbert order too: logical cell at hilbert
    # step k maps to physical cell at hilbert step k -> identity in
    # curve space; in raster space this is phys[path[k]] for logical
    # raster index raster(path[k]) — i.e. the permutation that makes
    # logically-close (hilbert) cells physically close.
    lin = path[:, 0] * m + path[:, 1]
    perm[lin] = lin[np.argsort(lin, kind="stable")]  # identity baseline
    # logical (i,j) -> physical hilbert position of (i,j)
    inv = np.empty(n * m, dtype=np.int64)
    inv[lin] = np.arange(n * m)
    return inv


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_app_mesh(num_devices: int | None = None, *, axis: str = "shards"):
    """1-D mesh for the curve-range-sharded data-mining apps.

    ``ops.kmeans_lloyd(..., mesh=)`` / ``ops.simjoin_pairs(..., mesh=)``
    shard contiguous curve ranges over this single axis.  Defaults to
    all visible devices; on a CPU container, simulate a multi-device
    host with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (set before jax import — the CI sharded job does exactly this).
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices) if num_devices is None else int(num_devices)
    if n <= 0 or n > len(devices):
        raise ValueError(
            f"num_devices={num_devices} out of range (have {len(devices)})"
        )
    return Mesh(np.asarray(devices[:n]), (axis,))
