"""Launch-layer tests: mesh construction, spec resolution, and a
small-scale lower+compile of every mode on the production mesh topology
(run in a subprocess so the 512-device XLA flag applies)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

# >100 s on CPU (the tinyllama production-mesh compile alone runs minutes);
# tier-1 runs `-m "not slow"`, CI still runs everything
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


class TestResolveSpec:
    def _mesh(self):
        import jax

        from repro.launch.mesh import make_production_mesh

        if len(jax.devices()) != 1:
            pytest.skip("spec tests run on the 1-device default backend")
        # a fake mesh object exposing names/shape is enough for resolve_spec
        class FakeMesh:
            axis_names = ("data", "model")
            devices = np.empty((16, 16), dtype=object)

        return FakeMesh()

    def test_drops_missing_axes(self):
        from repro.launch.steps import resolve_spec

        m = self._mesh()
        out = resolve_spec(P(("pod", "data"), None), (256, 128), m)
        assert out == P("data")

    def test_falls_back_on_indivisible(self):
        from repro.launch.steps import resolve_spec

        m = self._mesh()
        assert resolve_spec(P("model", "data"), (50280, 2560), m) == P(None, "data")
        assert resolve_spec(P(("pod", "data"),), (1,), m) == P()

    def test_keeps_divisible(self):
        from repro.launch.steps import resolve_spec

        m = self._mesh()
        assert resolve_spec(P("model", "data"), (50304, 2048), m) == P("model", "data")


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import dataclasses, json
    import jax
    from repro.configs import get_reduced
    from repro.configs.shapes import ShapeSpec
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import input_specs, jit_for_cell

    assert len(jax.devices()) == 512
    out = {}
    for multi_pod in (False, True):
        mesh = make_production_mesh(multi_pod=multi_pod)
        assert mesh.devices.shape == ((2,16,16) if multi_pod else (16,16))
        cfg = get_reduced("%ARCH%", d_model=256, num_heads=4, num_kv_heads=4,
                          head_dim=64, vocab_size=4096)
        for mode, seq, batch in (("train", 512, 64), ("prefill", 512, 32),
                                 ("decode", 512, 64)):
            if cfg.encoder_only and mode == "decode":
                continue
            shape = ShapeSpec(f"tiny_{mode}", seq, batch, mode)
            step = jit_for_cell(cfg, shape, mesh)
            compiled = step.lower(*input_specs(cfg, shape)).compile()
            txt = compiled.as_text()
            key = f"{'mp' if multi_pod else 'sp'}_{mode}"
            out[key] = {
                "collectives": ("all-reduce" in txt) or ("all-gather" in txt)
                                or ("reduce-scatter" in txt),
            }
    print("RESULT::" + json.dumps(out))
""")


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-2.7b"])
def test_production_mesh_compiles_all_modes(arch):
    """Reduced-size lower+compile across (mode × mesh)."""
    code = _SUBPROC.replace("%ARCH%", arch)
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=1200,
        env=_child_env(),
        cwd=REPO,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    payload = [l for l in res.stdout.splitlines() if l.startswith("RESULT::")]
    assert payload, res.stdout[-2000:]
    out = json.loads(payload[0][len("RESULT::"):])
    assert all(v["collectives"] for v in out.values()), out


def test_hilbert_grid_permutation_is_permutation():
    from repro.launch.mesh import hilbert_grid_permutation

    for n, m in ((4, 4), (16, 16), (8, 4)):
        perm = hilbert_grid_permutation(n, m)
        assert sorted(perm.tolist()) == list(range(n * m))
