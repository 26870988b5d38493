"""Per-arch smoke tests: reduced config, one forward + one grad step on CPU,
shape and finiteness asserts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, applicable_shapes, get_config, get_reduced, skip_reason

# ~100 s of per-arch grad compiles on CPU; tier-1 runs `-m "not slow"`,
# CI still runs everything
pytestmark = pytest.mark.slow
from repro.models import (
    cache_specs,
    count_params,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    param_count_analytic,
    param_specs,
)

ALL_ARCHS = sorted(ARCHS)
B, S = 2, 64


def make_batch(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {"labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.embed_inputs:
        batch["tokens"] = jax.random.randint(k1, (B, S), 0, cfg.vocab_size)
    else:
        batch["embeds"] = jax.random.normal(k3, (B, S, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = get_reduced(arch)
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        batch = make_batch(cfg, key)
        logits, aux = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
        assert logits.shape == (B, S, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all()), "NaN/inf in logits"
        assert bool(jnp.isfinite(aux)), "NaN aux loss"

    def test_one_grad_step(self, arch):
        cfg = get_reduced(arch)
        key = jax.random.PRNGKey(1)
        params = init_params(key, cfg)
        batch = make_batch(cfg, key)

        @jax.jit
        def step(p, b):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, b, cfg), has_aux=True
            )(p)
            p2 = jax.tree.map(lambda w, g: w - 1e-3 * g.astype(w.dtype), p, grads)
            return loss, p2

        loss, params2 = step(params, batch)
        assert bool(jnp.isfinite(loss))
        # a second step must change the loss (training is live)
        loss2, _ = step(params2, batch)
        assert float(loss2) != float(loss)

    def test_param_specs_cover_params(self, arch):
        cfg = get_reduced(arch)
        params = init_params(jax.random.PRNGKey(0), cfg)
        specs = param_specs(cfg)
        pl = jax.tree.structure(params)
        sl = jax.tree.structure(specs, is_leaf=lambda x: hasattr(x, "index"))
        assert pl == sl, f"param/spec tree mismatch:\n{pl}\nvs\n{sl}"
        # rank agreement: every spec has <= ndim entries
        for p, s in zip(jax.tree.leaves(params),
                        jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index"))):
            assert len(s) <= p.ndim, (p.shape, s)

    def test_analytic_param_count_matches(self, arch):
        cfg = get_reduced(arch)
        params = init_params(jax.random.PRNGKey(0), cfg)
        assert count_params(params) == param_count_analytic(cfg)


DECODE_ARCHS = [a for a in ALL_ARCHS if "decode_32k" in applicable_shapes(a)]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    """Greedy decode logits == full-forward logits position by position.

    Run in f32 so the check is semantic (the MLA absorbed-weight decode
    and the expanded training path differ by bf16 rounding otherwise).
    capacity_factor is raised so no MoE token is dropped — drop patterns
    legitimately differ between batched forward and per-token decode.
    """
    cfg = get_reduced(arch, dtype="float32", capacity_factor=8.0)
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    T = 8
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    full_logits, _ = forward(params, {"tokens": tokens}, cfg)

    cache = init_cache(cfg, B, T)
    step = jax.jit(lambda p, t, c, pos: decode_step(p, t, c, pos, cfg))
    for t in range(T):
        logits, cache = step(params, tokens[:, t : t + 1], cache, jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]), rtol=2e-3, atol=2e-3
        )


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_cache_specs_cover_cache(arch):
    shapes = applicable_shapes(arch)
    if not any(s.startswith(("decode", "long")) for s in shapes):
        pytest.skip("no decode shapes for this arch")
    cfg = get_reduced(arch)
    cache = init_cache(cfg, B, 16)
    specs = cache_specs(cfg)
    cl = jax.tree.structure(cache)
    sl = jax.tree.structure(specs, is_leaf=lambda x: hasattr(x, "index"))
    assert cl == sl


def test_skip_matrix_documented():
    """40 nominal cells; 31 runnable; 9 skipped with reasons."""
    cells = [(a, s) for a in ALL_ARCHS for s in
             ("train_4k", "prefill_32k", "decode_32k", "long_500k")]
    assert len(cells) == 40
    runnable = [(a, s) for a, s in cells if skip_reason(a, s) is None]
    skipped = [(a, s) for a, s in cells if skip_reason(a, s) is not None]
    assert len(runnable) == 31 and len(skipped) == 9
    for a, s in skipped:
        assert isinstance(skip_reason(a, s), str)


def test_full_configs_validate_and_count():
    """Full configs build (no allocation) and param counts are plausible."""
    expected_b = {
        "olmoe-1b-7b": (6, 8),
        "deepseek-v2-236b": (220, 250),
        "qwen2.5-14b": (13, 16),
        "minitron-8b": (7.5, 10.5),
        "tinyllama-1.1b": (1.0, 1.3),
        "stablelm-1.6b": (1.4, 2.0),
        "zamba2-2.7b": (2.2, 3.2),
        "chameleon-34b": (32, 36),
        "mamba2-2.7b": (2.4, 3.0),
        "hubert-xlarge": (0.9, 1.3),
    }
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        n = param_count_analytic(cfg) / 1e9
        lo, hi = expected_b[arch]
        assert lo <= n <= hi, f"{arch}: {n:.2f}B params out of [{lo},{hi}]"
