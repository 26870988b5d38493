"""shard_map expert-parallel MoE dispatch: exactness vs the single-device
path, gradient flow, and load conservation — on an 8-device submesh
(subprocess, so the device-count flag doesn't leak into other tests)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

# ~8 min of 8-device jit+grad compile on CPU; tier-1 runs `-m "not slow"`,
# CI still runs everything
pytestmark = pytest.mark.slow


REPO = pathlib.Path(__file__).resolve().parents[1]


def _child_env():
    """The parent's environment, held to the CPU: the child never loads
    the TPU library (one process at a time may hold it)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src")] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    return env

_CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.models.moe import init_moe, moe_forward
    from repro.models.sharding import activation_mesh

    cfg = get_reduced("olmoe-1b-7b", capacity_factor=64.0,
                      num_shared_experts=0, dtype="float32")
    params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, cfg.d_model)),
                    jnp.float32)
    ref, _ = moe_forward(params, x, cfg)

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    with mesh:
        with activation_mesh(mesh, ("data",)):
            out, aux = jax.jit(lambda p, x: moe_forward(p, x, cfg))(params, x)
            grads = jax.jit(jax.grad(
                lambda p, x: moe_forward(p, x, cfg)[0].sum()))(params, x)

    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, f"EP output mismatch: {err}"
    assert bool(jnp.isfinite(aux))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all())
    # expert grads must be nonzero (every rank's experts saw tokens)
    assert float(jnp.abs(grads["w_down"]).sum()) > 0
    print("EP-OK", err)
""")


def test_shard_map_ep_matches_dense():
    res = subprocess.run(
        [sys.executable, "-c", _CODE],
        capture_output=True, text=True, timeout=900,
        env=_child_env(),
        cwd=REPO,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "EP-OK" in res.stdout
