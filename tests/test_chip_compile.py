"""Compile rehearsal: every main-path Pallas kernel, at real widths,
compiled (``interpret=False``) for a described TPU v5e chip.

Nothing runs — the TPU compiler only lowers and compiles against a
topology that is described, not attached.  That catches what interpret
mode cannot: blocks that break the (8, 128) tiling rule, primitives with
no Mosaic lowering, and VMEM overruns.  The topology is described inside
a module-scoped fixture (never at import, in a ``parametrize`` or in a
``skipif``): only one process at a time may hold the TPU library, so a
worker that describes it while collecting would starve the others.
Every compile happens in this process; no child process is started.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    kmeans_schedule,
    phased_schedule,
    tile_schedule,
    tile_schedule_nd,
)
from repro.core.schedule import mark_first_visits

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    # compiles written to the persistent cache cannot be read back
    # without a chip; keep the cache out of the rehearsal
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------------------
# paged attention: tinyllama-1.1b GQA widths and the MLA latent width
# ---------------------------------------------------------------------------

# (Hkv, g, Dk, Dv, dtype of queries and pools, as the engine passes
# them): tinyllama-1.1b (32 q / 4 kv heads, head
# dim 64) and DeepSeek-V2 MLA (one latent "head", 128 query heads,
# kv_lora_rank 512 + rope 64 = 576)
ATTN_WIDTHS = {
    "gqa-tinyllama": (4, 8, 64, 64, jnp.bfloat16),
    "mla-deepseek": (1, 128, 576, 576, jnp.bfloat16),
}


@pytest.mark.parametrize("width", sorted(ATTN_WIDTHS))
def test_flash_decode_compiles(one_chip, width):
    from repro.kernels.attention import decode_page_schedule, flash_attention_decode

    Hkv, g, Dk, Dv, dt = ATTN_WIDTHS[width]
    B, ps, max_len = 8, 16, 2048
    MP = max_len // ps
    P = B * MP + 1
    sched = decode_page_schedule(B, MP)
    args = (
        _spec(sched.shape, jnp.int32, one_chip),
        _spec((B, MP), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((B, Hkv, g, Dk), dt, one_chip),
        _spec((P, Hkv, ps, Dk), dt, one_chip),
        _spec((P, Hkv, ps, Dv), dt, one_chip),
    )
    _compile(lambda *a: flash_attention_decode(*a, interpret=False), *args)


@pytest.mark.parametrize("width", sorted(ATTN_WIDTHS))
def test_flash_prefill_compiles(one_chip, width):
    from repro.kernels.attention import (
        flash_attention_prefill,
        prefill_page_schedule,
    )

    Hkv, g, Dk, Dv, dt = ATTN_WIDTHS[width]
    B, ps, max_len, T = 8, 16, 2048, 512
    MP = max_len // ps
    P = B * MP + 1
    sched = prefill_page_schedule([0] * B, [T] * B, ps, MP)
    args = (
        _spec(sched.shape, jnp.int32, one_chip),
        _spec((B, MP), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((B, T, Hkv, g, Dk), dt, one_chip),
        _spec((P, Hkv, ps, Dk), dt, one_chip),
        _spec((P, Hkv, ps, Dv), dt, one_chip),
    )
    _compile(lambda *a: flash_attention_prefill(*a, interpret=False), *args)


# ---------------------------------------------------------------------------
# fused §7 apps at working sets above VMEM
# ---------------------------------------------------------------------------

def test_fused_floyd_warshall_compiles(one_chip):
    from repro.kernels.floyd_warshall import floyd_warshall_blocked

    n = 4096
    d = _spec((n, n), jnp.float32, one_chip)
    _compile(lambda d: floyd_warshall_blocked(d, b=128, interpret=False), d)


def test_fused_cholesky_compiles(one_chip):
    from repro.kernels.cholesky import cholesky_blocked

    n = 4096
    a = _spec((n, n), jnp.float32, one_chip)
    _compile(lambda a: cholesky_blocked(a, b=128, interpret=False), a)


@pytest.mark.parametrize("schedule_ndim", [2, 3])
def test_matmul_compiles(one_chip, schedule_ndim):
    from repro.core import tile_schedule_device
    from repro.kernels.matmul import matmul_swizzled, matmul_swizzled_3d

    n, blk = 4096, 256
    nt = n // blk
    a = _spec((n, n), jnp.bfloat16, one_chip)
    if schedule_ndim == 2:
        sched = tile_schedule("fur", nt, nt)
        fn = matmul_swizzled
    else:
        sched = mark_first_visits(tile_schedule_nd("hilbert", (nt, nt, nt)), (0, 1))
        fn = matmul_swizzled_3d
    sd = _spec(sched.shape, jnp.int32, one_chip)
    _compile(
        lambda s, a, b: fn(s, a, b, bm=blk, bn=blk, bk=blk, interpret=False),
        sd, a, a,
    )


def test_fused_lloyd_compiles(one_chip):
    from repro.kernels.kmeans import kmeans_lloyd_fused

    N, D, K, bp, bc = 2**18, 16, 256, 256, 128
    sched = kmeans_schedule("fur", N // bp, K // bc)
    args = (
        _spec(sched.shape, jnp.int32, one_chip),
        _spec((N, D), jnp.float32, one_chip),
        _spec((K, D), jnp.float32, one_chip),
    )
    _compile(
        lambda s, x, c: kmeans_lloyd_fused(
            s, x, c, iters=1, bp=bp, bc=bc, interpret=False
        ),
        *args,
    )


# the simjoin-syn3d-2m.eps-k6 cell: 2,000,000 points (7,813 tiles of 256,
# the last ragged), ~131 k kept tile pairs (a 2^18-row schedule), pass 1 in
# blocks of PASS1_STEPS, pass 2 in blocks of 8192 table rows, 5,922,084
# pairs flattened 2^20 at a time
SJ_N, SJ_D, SJ_BP, SJ_P = 2_000_000, 3, 256, 5_922_084
SJ_NP = -(-SJ_N // SJ_BP) * SJ_BP


@pytest.mark.parametrize("pass_", ["hits", "emit", "flatten", "schedule"])
def test_simjoin_compiles(one_chip, pass_):
    if pass_ == "flatten":
        return _check_simjoin_flatten(one_chip)
    from repro.kernels.simjoin import (
        PASS1_STEPS,
        simjoin_emit_swizzled,
        simjoin_schedule,
        simjoin_totals,
    )

    x = _spec((SJ_NP, SJ_D), jnp.float32, one_chip)
    if pass_ == "schedule":
        # the prune of all 30.5 M lower-triangle tile pairs, on the device
        lowered = jax.jit(lambda x: simjoin_schedule(
            x, None, eps=18.3, bp=SJ_BP, n_valid=SJ_N, cap=1 << 18
        )).lower(x)
        assert " sort(" in lowered.compile().as_text()
        return
    if pass_ == "hits":
        # one pass-1 block: PASS1_STEPS steps of the schedule in SMEM, a
        # grid of the block's live steps
        sd = _spec((1 << 18, 2), jnp.int32, one_chip)
        fn = lambda s, n, x: simjoin_totals(  # noqa: E731
            s, PASS1_STEPS, n, x, steps=PASS1_STEPS, eps=18.3, bp=SJ_BP,
            n_valid=SJ_N, interpret=False,
        )
        _compile(fn, sd, _spec((), jnp.int32, one_chip), x)
        return
    else:
        # packed rows and row counts of one block of 8192 (i, j, live) rows
        sd = _spec((8192, 3), jnp.int32, one_chip)
        fn = lambda s, x: simjoin_emit_swizzled(  # noqa: E731
            s, x, eps=18.3, bp=SJ_BP, n_valid=SJ_N, interpret=False
        )
    _compile(fn, sd, x)


def _check_simjoin_flatten(one_chip):
    """One block of the flatten at the 2,000,000-point cell's size, the
    map back through the points' order included: 8192 table rows of
    256-point tiles, 2^20 pairs written into the 5,922,084-pair output.
    The flatten is one Mosaic kernel; the map back's two gathers take
    1-D indices (a gather indexed by (P, 2) compiles for minutes), and
    nothing sorts."""
    from repro.kernels.simjoin import simjoin_compact

    n, bp, cap = 8192, SJ_BP, 1 << 20
    lowered = jax.jit(
        lambda o, i, c, r, t, p: simjoin_compact(
            o, i, c, r, t, p, 0, bp=bp, cap=cap, interpret=False
        )
    ).lower(
        _spec((SJ_P + cap, 2), jnp.int32, one_chip),
        _spec((n, bp, bp), jnp.int8, one_chip),
        _spec((n, 1, bp), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((n, 2), jnp.int32, one_chip),
        _spec((SJ_N,), jnp.int32, one_chip),
    )
    gathers = [
        line for line in lowered.as_text().splitlines()
        if "stablehlo.gather" in line
    ]
    # the rows' counts, then both sides' map back through the order
    assert len(gathers) == 3, gathers
    for line in gathers:
        operands = line.split(") -> ")[0]
        index = re.findall(r"tensor<([0-9x]+)xi32>", operands)[-1]
        assert index.count("x") <= 1, line  # (k,) or (k, 1) indices
    compiled = lowered.compile().as_text()
    assert "tpu_custom_call" in compiled  # the flatten kernel
    assert " sort(" not in compiled
