"""System-level property tests (hypothesis): invariants that must hold for
ANY input, not just the curated cases.

* schedule-invariance: the swizzled matmul kernel must produce identical
  results under ANY bijective tile order — the correctness/performance
  separation at the heart of the design (order is a pure perf knob);
* Hilbert locality: |Δi|+|Δj| ≤ 3·√(Δh) (the classic locality bound —
  nearby order values are nearby in space);
* work-range splitting: Hilbert-keyed work-stealing ranges cover exactly;
* elastic reshard: trainer state survives a mesh change bit-exactly.
"""
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property suite skipped: install the [test] extra (pip install -e .[test]) — CI runs these",
)
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import hilbert_decode
from repro.kernels import ops, ref


class TestScheduleInvariance:
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_matmul_any_bijective_order(self, seed):
        """A uniformly random permutation of the tile grid — far harsher
        than any space-filling curve — must give the same product."""
        rng = np.random.default_rng(seed)
        mt, nt, bm, bn, bk = 4, 3, 16, 16, 16
        perm = rng.permutation(mt * nt)
        i, j = np.divmod(perm, nt)
        sched = jnp.asarray(np.stack([i, j], 1), jnp.int32)
        a = jnp.asarray(rng.normal(size=(mt * bm, 2 * bk)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(2 * bk, nt * bn)), jnp.float32)
        from repro.kernels.matmul import matmul_swizzled

        out = matmul_swizzled(sched, a, b, bm=bm, bn=bn, bk=bk, interpret=True)
        np.testing.assert_allclose(out, ref.matmul(a, b), rtol=1e-4, atol=1e-4)

    @given(st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=5, deadline=None)
    def test_kmeans_any_bijective_order(self, seed):
        rng = np.random.default_rng(seed)
        pt, ct, bp, bc = 3, 2, 32, 16
        perm = rng.permutation(pt * ct)
        i, j = np.divmod(perm, ct)
        sched = jnp.asarray(np.stack([i, j], 1), jnp.int32)
        x = jnp.asarray(rng.normal(size=(pt * bp, 8)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(ct * bc, 8)), jnp.float32)
        from repro.kernels.kmeans import kmeans_assign_swizzled

        _, assign = kmeans_assign_swizzled(sched, x, c, bp=bp, bc=bc,
                                           interpret=True)
        np.testing.assert_array_equal(assign, ref.kmeans_assign(x, c)[1])


class TestHilbertLocality:
    @given(
        st.integers(min_value=0, max_value=4**10 - 2),
        st.integers(min_value=1, max_value=256),
    )
    @settings(max_examples=150, deadline=None)
    def test_locality_bound(self, h, dh):
        """Hilbert curve locality: grid distance ≤ 3·sqrt(order distance)."""
        i0, j0 = hilbert_decode(h)
        i1, j1 = hilbert_decode(h + dh)
        assert abs(i1 - i0) + abs(j1 - j0) <= 3.0 * np.sqrt(dh) + 1


class TestWorkRanges:
    @given(st.integers(min_value=1, max_value=64), st.integers(min_value=1, max_value=12))
    @settings(max_examples=40, deadline=None)
    def test_cover_exactly(self, n_items, n_workers):
        import tempfile

        from repro.configs import get_reduced
        from repro.train import Trainer, TrainerConfig

        cfg = get_reduced("tinyllama-1.1b", num_layers=1, d_model=32,
                          num_heads=1, num_kv_heads=1, head_dim=32,
                          d_ff=64, vocab_size=64)
        with tempfile.TemporaryDirectory() as d:
            tr = Trainer(cfg, TrainerConfig(grad_accum=n_items,
                                            micro_batch=1, seq_len=8,
                                            ckpt_dir=d))
            ranges = tr.work_ranges(n_workers)
        assert ranges[0][0] == 0 and ranges[-1][1] == n_items
        for (a, b), (c, d_) in zip(ranges[:-1], ranges[1:]):
            assert b == c and a <= b


REPO = pathlib.Path(__file__).resolve().parents[1]


def _child_env():
    """The parent's environment, held to the CPU: the child never loads
    the TPU library (one process at a time may hold it)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src")] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    return env


def test_elastic_reshard_roundtrip():
    """Trainer state survives a simulated topology change bit-exactly
    (8 placeholder devices, 4x2 -> 2x4 mesh)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.train import Trainer, TrainerConfig

        cfg = get_reduced("tinyllama-1.1b", num_layers=2, d_model=64,
                          num_heads=2, num_kv_heads=2, head_dim=32,
                          d_ff=128, vocab_size=128)
        auto = (jax.sharding.AxisType.Auto,) * 2
        with tempfile.TemporaryDirectory() as d:
            tcfg = TrainerConfig(micro_batch=8, seq_len=16, ckpt_dir=d)
            m1 = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
            tr = Trainer(cfg, tcfg, mesh=m1)
            state = tr.init_state(0)
            state, _ = tr._step_fn(state, tr.batch_at(0))
            before = jax.device_get(state["params"])

            m2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=auto)
            state2 = tr.reshard(state, m2)
            after = jax.device_get(state2["params"])
            for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            # training continues on the new mesh
            state2, metrics = tr._step_fn(state2, tr.batch_at(1))
            assert bool(jnp.isfinite(metrics["loss"]))
        print("RESHARD-OK")
    """)
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env=_child_env(),
        cwd=REPO,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "RESHARD-OK" in res.stdout
