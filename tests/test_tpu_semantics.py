"""The main-path kernels under the TPU-semantics interpreter.

``interpret=True`` re-fetches a revisited output block from HBM, which
the chip's pipeline never does (a revisit sees whatever the VMEM buffer
last held), so a kernel can pass every interpret-mode test and still
read stale data on a TPU.  ``pltpu.InterpretParams()`` models the chip
instead: it refuses a revisited output block outright.  Every kernel
here runs once under it at a small size and must agree bit for bit
with the plain interpreter.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops

TPU = pltpu.InterpretParams()
RNG = np.random.default_rng(7)


def same(run):
    np.testing.assert_array_equal(np.asarray(run(TPU)), np.asarray(run(True)))


def test_revisited_output_block_is_refused():
    # the contract this file leans on: a non-consecutive revisit of an
    # output block is an error under the TPU-semantics interpreter
    import jax
    from jax.experimental import pallas as pl

    def kernel(sr, o_ref):
        o_ref[...] = o_ref[...] + 1.0

    spec = pl.BlockSpec((8, 128), lambda s, sr: (sr[s], 0))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(3,), in_specs=[], out_specs=spec
        ),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        interpret=TPU,
    )
    with pytest.raises(Exception, match="Revisited block"):
        np.asarray(call(jnp.asarray([0, 1, 0], jnp.int32)))


def test_floyd_warshall():
    n = 64
    w = RNG.integers(1, 10, (n, n)).astype(np.float32)
    d = np.where(RNG.uniform(size=(n, n)) < 0.2, w, np.inf).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    same(lambda m: ops.floyd_warshall(jnp.asarray(d), b=16, interpret=m))


@pytest.mark.parametrize("fused", [True, False])
def test_cholesky(fused):
    m = RNG.normal(size=(64, 64)).astype(np.float32)
    a = jnp.asarray(m @ m.T + 64 * np.eye(64, dtype=np.float32))
    same(lambda mode: ops.cholesky(a, b=16, fused=fused, interpret=mode))


def test_kmeans_lloyd():
    x = jnp.asarray(RNG.normal(size=(300, 5)), jnp.float32)
    same(lambda m: ops.kmeans_lloyd(x, 6, iters=2, bp=64, bc=2,
                                    interpret=m)[0])


@pytest.mark.parametrize("ndim", [2, 3])
def test_matmul(ndim):
    a = jnp.asarray(RNG.normal(size=(64, 96)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(96, 32)), jnp.float32)
    same(lambda m: ops.matmul(a, b, bm=16, bn=16, bk=16, schedule_ndim=ndim,
                              curve="hilbert", interpret=m))


def test_simjoin_pairs():
    x = jnp.asarray(RNG.normal(size=(150, 3)) * 0.5, jnp.float32)
    same(lambda m: ops.simjoin_pairs(x, eps=0.5, bp=32, interpret=m))


def test_simjoin_pairs_in_blocks(monkeypatch):
    # pass 1 in blocks of 4 steps (the last running only its live steps)
    # and pass 2 in blocks of 2 table rows, on Hilbert-sorted points
    from repro.kernels import simjoin

    monkeypatch.setattr(simjoin, "PASS1_STEPS", 4)
    monkeypatch.setattr(simjoin, "PASS2_CELLS", 2 * 32 * 32)
    x = jnp.asarray(RNG.normal(size=(150, 3)) * 0.5, jnp.float32)
    same(lambda m: ops.simjoin_pairs(x, eps=0.5, bp=32, hilbert_order=True,
                                     interpret=m))


def test_simjoin_flatten_reads_rows_out_of_order():
    # the sharded joins' flatten: listed rows across shards, out of
    # order, skipping some, with runs that cross the carried row
    from repro.kernels.simjoin import _id_code, pairs_from_masks

    bp = 32
    masks = RNG.uniform(size=(6, bp, bp)) < 0.1
    rows, tiles = np.array([4, 0, 2, 5]), np.array([[3, 1], [0, 0], [2, 2], [7, 5]])
    dtype, bias = _id_code(bp)
    ids = RNG.integers(0, bp, (6, bp, bp))
    for t, r in np.ndindex(6, bp):
        nz = np.flatnonzero(masks[t, r])
        ids[t, r, :len(nz)] = nz
    ids = jnp.asarray((ids - bias).astype(dtype))
    counts = jnp.asarray(masks.sum(axis=2).astype(np.int32)[:, None, :])
    P = int(masks[rows].sum())
    same(lambda m: pairs_from_masks(ids, counts, rows, tiles, P, bp, interpret=m))


def test_paged_decode_and_prefill():
    from repro.kernels.attention import (
        decode_page_schedule,
        flash_attention_decode,
        flash_attention_prefill,
        prefill_page_schedule,
    )

    B, Hkv, g, D, ps, MP, P = 3, 2, 4, 32, 8, 4, 16
    pt = jnp.asarray(RNG.permutation(np.arange(1, P))[: B * MP].reshape(B, MP),
                     jnp.int32)
    kp = jnp.asarray(RNG.normal(size=(P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(P, Hkv, ps, D)), jnp.float32)
    pos = jnp.asarray([3, 17, 30], jnp.int32)
    q = jnp.asarray(RNG.normal(size=(B, Hkv, g, D)), jnp.float32)
    sched = jnp.asarray(decode_page_schedule(B, MP))
    same(lambda m: flash_attention_decode(sched, pt, pos, q, kp, vp,
                                          interpret=m))
    # a ragged cohort: the schedule is padded past its real rows
    pos0, n_new, T = [0, 5, 0], [13, 9, 0], 16
    psched = prefill_page_schedule(pos0, n_new, ps, MP)
    assert psched[:, 5].sum() < len(psched)  # pad rows present
    qp = jnp.asarray(RNG.normal(size=(B, T, Hkv, g, D)), jnp.float32)
    rows = np.arange(T)[None, :] < np.asarray(n_new)[:, None]

    def prefill(m):
        out = flash_attention_prefill(
            jnp.asarray(psched), pt, jnp.asarray(pos0, jnp.int32), qp, kp, vp,
            interpret=m,
        )
        return np.where(rows[:, :, None, None, None], np.asarray(out), 0.0)

    same(prefill)
