"""E5b — beyond-paper: Hilbert device layout for the ICI torus.

Logical (data, model) neighbours should be physically adjacent on the 2-D
torus.  We compare torus hop counts between raster (default) and
FUR-Hilbert device orderings for the collective patterns the framework
uses: ring all-reduce over each mesh axis row/column.
"""
from __future__ import annotations

import numpy as np

from repro.launch.mesh import hilbert_grid_permutation


def _phys_coords(n: int, m: int, perm: np.ndarray) -> np.ndarray:
    """perm[logical_linear] = physical_linear; physical grid row-major."""
    phys = perm.reshape(n, m)
    return np.stack([phys // m, phys % m], axis=-1)  # (n, m, 2)


def _torus_hops(a: np.ndarray, b: np.ndarray, n: int, m: int) -> int:
    d0 = np.abs(a[..., 0] - b[..., 0])
    d1 = np.abs(a[..., 1] - b[..., 1])
    return int(np.sum(np.minimum(d0, n - d0) + np.minimum(d1, m - d1)))


def run(n: int = 16, m: int = 16) -> list[dict]:
    rows = []
    raster = np.arange(n * m, dtype=np.int64)
    hilb = hilbert_grid_permutation(n, m)
    for name, perm in (("raster", raster), ("hilbert", hilb)):
        c = _phys_coords(n, m, perm)
        # ring neighbours along the logical "model" axis (rows) and
        # "data" axis (columns), wrap-around included
        hops_model = _torus_hops(c, np.roll(c, -1, axis=1), n, m)
        hops_data = _torus_hops(c, np.roll(c, -1, axis=0), n, m)
        rows.append({
            "bench": "mesh_layout", "name": f"{name}_ring_hops",
            "value": hops_model + hops_data,
            "derived": f"model-axis={hops_model} data-axis={hops_data}",
        })
    rows += _halo_bytes_rows()
    return rows


def _halo_bytes_rows() -> list[dict]:
    """Halo-exchange traffic of the sharded ε-join as the mesh widens:
    bytes per shard for boundary strips at every simulable mesh size,
    beside the point buffer's bytes that replicating x would move per
    pass.  More shards → narrower resident curve ranges → more boundary
    per shard; replication is flat (every shard always receives all of
    x).  jax is imported lazily so the hop-count rows above stay
    numpy-only."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.sharded import simjoin_sharded_volume
    from repro.launch.mesh import make_app_mesh

    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.uniform(size=(1024, 2)), jnp.float32)
    rows = []
    for s in (1, 2, 4, 8):
        if s > len(jax.devices()):
            continue
        mesh = make_app_mesh(s)
        kw = dict(mesh=mesh, bp=64, hilbert_order=True, interpret=True)
        vh = simjoin_sharded_volume(x, 0.04, **kw)
        rows.append({
            "bench": "mesh_halo", "name": f"simjoin_halo_bytes_mesh{s}",
            "value": int(vh["bytes_per_shard"]),
            "bytes_per_shard": int(vh["bytes_per_shard"]),
            "point_bytes": int(x.nbytes),
            "derived": f"bytes/shard boundary strips (replicated "
                       f"{2 * x.nbytes}: the point buffer once per pass); "
                       f"N=1024 uniform 2-D",
        })
    return rows
