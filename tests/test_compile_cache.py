"""The persistent compilation cache has one place per run:
``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<checkout>/.jax_cache``."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_default_is_the_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_stays_in_charge(tmp_path):
    # a child on the CPU, so the cache it fills is its own
    target = tmp_path / "x"
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import use_compile_cache
        print("DIR=" + use_compile_cache())
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
        print("CFG=" + str(jax.config.jax_compilation_cache_dir))
    """)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(target),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=os.pathsep.join(
            [str(REPO / "src")] + [p for p in [os.environ.get("PYTHONPATH")] if p]
        ),
    )
    default = REPO / ".jax_cache"
    listing = lambda: sorted(default.iterdir()) if default.is_dir() else []  # noqa: E731
    before = listing()
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=tmp_path)
    assert res.returncode == 0, res.stderr[-3000:]
    assert f"DIR={target}" in res.stdout
    assert f"CFG={target}" in res.stdout
    assert any(p.name.endswith("-cache") for p in target.iterdir())
    assert listing() == before  # and nowhere else
