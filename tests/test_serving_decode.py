"""Serving decode differentials: flash/paged decode vs the retained XLA
path, the paged KV allocator, and the engine's continuous-batching modes.

Every new decode path added by the Hilbert-paged serving work is pinned
to the dense XLA `_sdpa` decode the same way the fused apps are pinned
to their reference oracles:

  * kernel level   — flash_attention_decode vs a numpy oracle over a
    ragged page table (trash-page entries included);
  * step level     — decode_step_paged (flash AND xla-gather) vs
    decode_step, GQA and MLA, ragged per-slot positions;
  * engine level   — ≥64-step greedy rollouts token-identical across
    dense / paged-xla / flash-paged, plus slot eviction/re-admission.

Engine rollouts compare engine modes run in the SAME process with
module-level shared jit executables per (cfg, mode) — the cross-program
ulp-drift lesson from the PR-5 serving flakes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels import ops
from repro.kernels.attention import decode_page_schedule, flash_attention_decode
from repro.models import (
    decode_step,
    decode_step_paged,
    init_cache,
    init_paged_cache,
    init_params,
)
from repro.serve import PagedKVCache, ServeEngine
from repro.serve.kv_pages import TRASH_PAGE

GQA = "tinyllama-1.1b"
MLA = "deepseek-v2-236b"


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

class TestDecodeKernel:
    def test_vs_numpy_oracle_ragged(self):
        B, Hkv, g, Dk, ps, MP, P = 3, 2, 4, 32, 8, 4, 16
        rng = np.random.default_rng(0)
        pos = jnp.asarray([0, 11, 30], dtype=jnp.int32)
        pt = np.zeros((B, MP), dtype=np.int32)
        pt[0, 0] = 3
        pt[1, :2] = [5, 1]
        pt[2, :] = [7, 2, 9, 4]
        q = jnp.asarray(rng.normal(size=(B, Hkv, g, Dk)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dk)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dk)), jnp.float32)
        sched = jnp.asarray(decode_page_schedule(B, MP))
        out = flash_attention_decode(
            sched, jnp.asarray(pt), pos, q, kp, vp, interpret=True
        )
        for b in range(B):
            n = int(pos[b]) + 1
            # (ps, Hkv, Dk) token-major view of each head-major page
            kt = np.asarray(kp).transpose(0, 2, 1, 3)
            vt = np.asarray(vp).transpose(0, 2, 1, 3)
            ks = np.concatenate([kt[pt[b, i]] for i in range(MP)])[:n]
            vs = np.concatenate([vt[pt[b, i]] for i in range(MP)])[:n]
            for h in range(Hkv):
                s = np.asarray(q)[b, h] @ ks[:, h].T / np.sqrt(Dk)
                p = np.exp(s - s.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                ref = p @ vs[:, h]
                np.testing.assert_allclose(
                    np.asarray(out)[b, h], ref, atol=2e-6, rtol=1e-5
                )

    def test_trash_page_content_irrelevant(self):
        """Unallocated table entries point at page 0; poisoning page 0
        must not change the output (positional masking, not gather
        branching)."""
        B, Hkv, g, Dk, ps, MP, P = 2, 1, 2, 16, 4, 3, 8
        rng = np.random.default_rng(1)
        pos = jnp.asarray([2, 5], dtype=jnp.int32)
        pt = np.zeros((B, MP), dtype=np.int32)
        pt[0, 0] = 1
        pt[1, :2] = [2, 3]
        q = jnp.asarray(rng.normal(size=(B, Hkv, g, Dk)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dk)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dk)), jnp.float32)
        sched = jnp.asarray(decode_page_schedule(B, MP))
        out = flash_attention_decode(
            sched, jnp.asarray(pt), pos, q, kp, vp, interpret=True
        )
        kp2 = kp.at[TRASH_PAGE].set(1e9)
        vp2 = vp.at[TRASH_PAGE].set(-1e9)
        out2 = flash_attention_decode(
            sched, jnp.asarray(pt), pos, q, kp2, vp2, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# ops production surface
# ---------------------------------------------------------------------------

class TestOpsSurface:
    def _ref(self, q, k, v, kv_len, causal):
        B, H, S, D = q.shape
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        m = (jnp.arange(S)[None, :] < kv_len[:, None])[:, None, None, :]
        if causal:
            m = m & (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])[None, None]
        scores = jnp.where(m, scores, -jnp.inf)
        return jnp.einsum(
            "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v
        )

    @pytest.mark.parametrize("mask_type", ["padding", "padding_causal"])
    def test_mask_types_vs_reference(self, mask_type):
        B, H, S, D = 2, 4, 48, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        kv_len = jnp.asarray([17, 48], dtype=jnp.int32)
        out = ops.attention(q, k, v, mask_type=mask_type, kv_seqlen=kv_len)
        ref = self._ref(q, k, v, kv_len, causal="causal" in mask_type)
        valid_q = jnp.arange(S)[None, :] < kv_len[:, None]
        err = jnp.where(valid_q[:, None, :, None], out - ref, 0)
        np.testing.assert_allclose(np.asarray(err), 0, atol=2e-6)

    def test_q_seqlen_zeroes_tail_rows(self):
        B, H, S, D = 2, 2, 32, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        kv_len = jnp.asarray([9, 32], dtype=jnp.int32)
        out = ops.attention(
            q, k, v, mask_type="padding", kv_seqlen=kv_len, q_seqlen=kv_len
        )
        assert bool(jnp.all(out[0, :, 9:] == 0))
        assert bool(jnp.any(out[0, :, :9] != 0))

    def test_mask_type_validation(self):
        q = jnp.zeros((1, 1, 16, 16))
        with pytest.raises(ValueError, match="mask_type"):
            ops.attention(q, q, q, mask_type="banded")
        with pytest.raises(ValueError, match="kv_seqlen"):
            ops.attention(q, q, q, mask_type="padding")


# ---------------------------------------------------------------------------
# paged KV allocator
# ---------------------------------------------------------------------------

class TestKVPages:
    def test_alloc_free_trash(self):
        c = PagedKVCache(4, 4, 8, layout="hilbert")
        p0 = c.ensure_pos(0, 0)
        assert p0 != TRASH_PAGE
        assert c.ensure_pos(0, 7) == p0  # same page
        p1 = c.ensure_pos(0, 8)
        assert p1 != p0 and c.pages_used[0] == 2
        t = c.device_table()
        assert t.shape == (4, 4)
        assert int(t[0, 0]) == p0 and int(t[0, 2]) == TRASH_PAGE
        assert c.device_table() is t  # cached until mutation
        assert c.free_slot(0) == 2
        assert c.num_free == 16
        assert int(c.device_table()[0, 0]) == TRASH_PAGE

    def test_pages_distinct_across_slots(self):
        c = PagedKVCache(4, 4, 8, layout="hilbert")
        for s in range(4):
            c.ensure_pos(s, 31)
        phys = c.page_table[c.page_table != TRASH_PAGE]
        assert len(set(phys.tolist())) == phys.size == 16

    def test_exhaustion_raises(self):
        c = PagedKVCache(2, 2, 4, num_pages=3, layout="naive")
        c.ensure_pos(0, 7)
        with pytest.raises(MemoryError):
            c.ensure_pos(1, 0)

    def test_hilbert_layout_fewer_runs_under_churn(self):
        """The measurable locality claim: under interleaved slot growth
        with eviction churn (the serving access pattern), the curve
        layout's decode gather stream has fewer contiguous memory runs
        than naive first-fit.  Deterministic given the seeds."""

        def churn(layout, seed):
            rng = np.random.default_rng(seed)
            B, MP, ps = 8, 8, 16
            c = PagedKVCache(B, MP, ps, layout=layout)
            pos = np.zeros(B, dtype=int)
            for s in range(B):
                c.ensure_pos(s, 0)
            for _ in range(400):
                for s in range(B):
                    pos[s] += 1
                    if pos[s] >= MP * ps - 1:
                        c.free_slot(s)
                        pos[s] = int(rng.integers(0, ps))
                    c.ensure_pos(s, int(pos[s]))
                if rng.random() < 0.05:
                    s = int(rng.integers(0, B))
                    c.free_slot(s)
                    pos[s] = 0
                    c.ensure_pos(s, 0)
            return c.gather_runs()

        h = np.mean([churn("hilbert", s) for s in range(10)])
        n = np.mean([churn("naive", s) for s in range(10)])
        assert h < n, (h, n)


# ---------------------------------------------------------------------------
# step-level differentials
# ---------------------------------------------------------------------------

class TestPagedDecodeStep:
    @pytest.mark.parametrize("arch", [GQA, MLA])
    @pytest.mark.parametrize("attn_impl", ["flash", "xla"])
    def test_paged_step_matches_dense(self, arch, attn_impl):
        cfg = get_reduced(arch, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        B, ps, MP = 4, 8, 4
        pos = jnp.asarray([0, 5, 12, 22], dtype=jnp.int32)
        dense = init_cache(cfg, B, ps * MP)
        kvc = PagedKVCache(B, MP, ps, layout="hilbert")
        for s in range(B):
            kvc.ensure_pos(s, int(pos[s]))
        pt = kvc.device_table()
        pages = init_paged_cache(cfg, kvc.num_pages, ps)
        # two history tokens per slot so the ragged depths hold real KV
        for d in (2, 1):
            hp = jnp.maximum(pos - d, 0)
            htok = jax.random.randint(jax.random.PRNGKey(d), (B, 1), 0, cfg.vocab_size)
            _, dense = decode_step(params, htok, dense, hp, cfg)
            _, pages = decode_step_paged(
                params, htok, pages, hp, pt, cfg, attn_impl=attn_impl
            )
        tok = jax.random.randint(jax.random.PRNGKey(9), (B, 1), 0, cfg.vocab_size)
        lg_d, _ = decode_step(params, tok, dense, pos, cfg)
        lg_p, _ = decode_step_paged(
            params, tok, pages, pos, pt, cfg, attn_impl=attn_impl
        )
        np.testing.assert_allclose(
            np.asarray(lg_p), np.asarray(lg_d), atol=2e-5, rtol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(lg_p, -1)), np.asarray(jnp.argmax(lg_d, -1))
        )


# ---------------------------------------------------------------------------
# engine-level differentials
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("page_size", 16)
    return ServeEngine(cfg, params, **kw)


MODES = [
    ("dense", dict(paged=False)),
    ("paged-xla", dict(paged=True, attn_impl="xla")),
    ("flash-paged", dict(paged=True, attn_impl="flash")),
]


class TestEngineModes:
    @pytest.mark.parametrize("arch", [GQA, MLA])
    def test_64_step_rollout_token_identical(self, arch):
        """Acceptance: ≥64-step greedy rollouts token-identical across
        dense / paged-xla / flash-paged, GQA and MLA."""
        cfg = get_reduced(arch, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        outs = {}
        for name, kw in MODES:
            eng = _engine(cfg, params, **kw)
            r1 = eng.submit([3, 17, 42], max_new=64)
            r2 = eng.submit([30, 2, 8, 11, 7], max_new=64)
            eng.run_until_done()
            assert len(r1.out) == 64 and len(r2.out) == 64
            outs[name] = (r1.out, r2.out)
        assert outs["paged-xla"] == outs["dense"]
        assert outs["flash-paged"] == outs["dense"]

    def test_eviction_readmission_token_identical(self):
        """4 requests over 2 slots: every slot is evicted and re-admitted
        with recycled physical pages; outputs must match dense exactly."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[3, 17, 42], [30, 2, 8, 11, 7], [5, 9], [1, 2, 3, 4]]
        outs = {}
        for name, kw in MODES:
            eng = _engine(cfg, params, **kw)
            reqs = [eng.submit(p, max_new=8) for p in prompts]
            eng.run_until_done()
            outs[name] = [r.out for r in reqs]
        assert outs["paged-xla"] == outs["dense"]
        assert outs["flash-paged"] == outs["dense"]
        # all pages returned after the last eviction
        eng = _engine(cfg, params, paged=True)
        for p in prompts:
            eng.submit(p, max_new=4)
        eng.run_until_done()
        assert eng.kv_pages.num_free == eng.kv_pages.num_pages - 1

    def test_admission_fifo_order(self):
        """The deque-backed queue admits strictly in submission order."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = _engine(cfg, params, paged=True)
        reqs = [eng.submit([5 + i], max_new=2) for i in range(5)]
        eng.run_until_done()
        assert eng.admitted == [r.rid for r in reqs]
        assert all(r.done for r in reqs)

    def test_chunked_prefill_matches_token_by_token(self):
        """prefill_chunk=1 (the old token-by-token schedule) and
        prefill_chunk=8 leave identical cache state and positions —
        chunking is a dispatch-count optimisation, not a math change.
        Compared on the CACHE, not rollout tokens: chunk sizes compile
        different programs, and cross-program greedy chains can flip on
        ulp ties (the PR-5 lesson)."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompt = list(range(1, 12))
        caches = []
        for chunk in (1, 8):
            eng = _engine(cfg, params, paged=True, prefill_chunk=chunk)
            eng.submit(prompt, max_new=4)
            eng._attach()
            # drop the trash page: masked lanes of different chunkings
            # divert different garbage into it (by design — it is never
            # attended), so only real pages must agree
            caches.append(jax.tree.map(lambda x: np.asarray(x)[:, 1:], eng.cache))
            assert eng.pos[0] == len(prompt) - 1
        for a, b in zip(jax.tree.leaves(caches[0]), jax.tree.leaves(caches[1])):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_hilbert_admission_preserves_outputs(self):
        """Hilbert token batching reorders which slot a request lands in,
        never what it generates."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[40, 41, 42], [3, 1, 2], [40, 40, 40], [7, 8]]

        def run(**kw):
            eng = _engine(cfg, params, paged=True, num_slots=4, **kw)
            reqs = [eng.submit(p, max_new=6) for p in prompts]
            eng.run_until_done()
            return [r.out for r in reqs]

        assert run(hilbert_admission=True) == run(hilbert_admission=False)

    def test_paged_rejects_recurrent_archs(self):
        cfg = get_reduced("mamba2-2.7b", dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="pure attention"):
            ServeEngine(cfg, params, paged=True)


# ---------------------------------------------------------------------------
# prefill kernel (compiled-forward batched prefill)
# ---------------------------------------------------------------------------

class TestPrefillKernel:
    def _setup(self, seed=0):
        B, Hkv, g, Dk, ps, MP, P = 2, 2, 3, 16, 8, 4, 12
        rng = np.random.default_rng(seed)
        pos0 = np.array([3, 0], dtype=np.int32)
        n_new = np.array([5, 9], dtype=np.int32)
        pt = np.zeros((B, MP), dtype=np.int32)
        pt[0, 0] = 4
        pt[1, :2] = [7, 2]
        T = 16  # two q tiles of bq=ps
        q = rng.normal(size=(B, T, Hkv, g, Dk)).astype(np.float32)
        kp = rng.normal(size=(P, Hkv, ps, Dk)).astype(np.float32)
        vp = rng.normal(size=(P, Hkv, ps, Dk)).astype(np.float32)
        return B, Hkv, g, Dk, ps, MP, pos0, n_new, pt, q, kp, vp

    def _run(self, pos0, n_new, ps, MP, pt, q, kp, vp):
        from repro.kernels.attention import (
            flash_attention_prefill,
            prefill_page_schedule,
        )

        sched = jnp.asarray(prefill_page_schedule(pos0, n_new, ps, MP))
        return flash_attention_prefill(
            sched, jnp.asarray(pt), jnp.asarray(pos0), jnp.asarray(q),
            jnp.asarray(kp), jnp.asarray(vp), interpret=True,
        )

    def test_vs_numpy_oracle_ragged(self):
        B, Hkv, g, Dk, ps, MP, pos0, n_new, pt, q, kp, vp = self._setup()
        out = np.asarray(self._run(pos0, n_new, ps, MP, pt, q, kp, vp))
        for b in range(B):
            # (ps, Hkv, Dk) token-major view of each head-major page
            ks = np.concatenate([kp[pt[b, i]].transpose(1, 0, 2) for i in range(MP)])
            vs = np.concatenate([vp[pt[b, i]].transpose(1, 0, 2) for i in range(MP)])
            for i in range(int(n_new[b])):
                qpos = int(pos0[b]) + i
                for h in range(Hkv):
                    s = q[b, i, h] @ ks[: qpos + 1, h].T / np.sqrt(Dk)
                    p = np.exp(s - s.max(-1, keepdims=True))
                    p /= p.sum(-1, keepdims=True)
                    ref = p @ vs[: qpos + 1, h]
                    np.testing.assert_allclose(
                        out[b, i, h], ref, atol=2e-6, rtol=1e-5
                    )

    def test_trash_page_content_irrelevant(self):
        """The schedule only visits a slot's allocated pages, so even a
        NaN-poisoned trash page cannot perturb prefill outputs."""
        B, Hkv, g, Dk, ps, MP, pos0, n_new, pt, q, kp, vp = self._setup(1)
        base = np.asarray(self._run(pos0, n_new, ps, MP, pt, q, kp, vp))
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[TRASH_PAGE] = np.nan
        vp2[TRASH_PAGE] = np.nan
        poisoned = np.asarray(self._run(pos0, n_new, ps, MP, pt, q, kp2, vp2))
        for b in range(B):
            n = int(n_new[b])
            np.testing.assert_array_equal(base[:, :n], poisoned[:, :n])


class TestScheduleDeviceCache:
    def test_first_call_under_jit_is_not_a_tracer(self):
        """A first call from inside a jit trace must cache a concrete
        device table, not pin the trace's tracer for later callers."""
        from repro.core.schedule import schedule_cache_clear
        from repro.kernels.attention import (
            decode_page_schedule,
            decode_page_schedule_device,
        )

        schedule_cache_clear()

        @jax.jit
        def f(x):
            return x + decode_page_schedule_device(2, 3).sum()

        f(jnp.float32(0))  # first call happens under the trace
        dev = decode_page_schedule_device(2, 3)
        assert not isinstance(dev, jax.core.Tracer)
        np.testing.assert_array_equal(
            np.asarray(dev), decode_page_schedule(2, 3)
        )


# ---------------------------------------------------------------------------
# prefix sharing: allocator level
# ---------------------------------------------------------------------------

class TestKVPagesSharing:
    def test_share_register_roundtrip(self):
        c = PagedKVCache(2, 4, 8)
        toks = list(range(19))
        c.ensure_pos(0, 18)
        assert c.register_prefix(0, toks) == 2  # 19 toks -> 2 full pages
        m = c.share_prefix(1, toks)
        assert m == 16
        assert c.page_table[1, 0] == c.page_table[0, 0]
        assert c.page_table[1, 1] == c.page_table[0, 1]
        # owner + trie retention + sharer
        assert c.refcount[c.page_table[0, 0]] == 3
        # the sharer only allocates its tail page
        before = c.stat_allocated
        c.ensure_pos(1, 18)
        assert c.stat_allocated == before + 1

    def test_partial_page_match_then_cow(self):
        c = PagedKVCache(2, 4, 8)
        donor = list(range(16))
        c.ensure_pos(0, 15)
        c.register_prefix(0, donor)
        # second prompt shares only the first 11 tokens of page 1
        taker = donor[:11] + [99, 98, 97]
        m = c.share_prefix(1, taker)
        assert m == 11  # page 0 exact + 3-token partial of page 1
        shared = int(c.page_table[1, 1])
        assert shared == int(c.page_table[0, 1])
        # first divergent write triggers COW on the partially-shared page
        pairs = c.prepare_write(1, 11, 14)
        assert len(pairs) == 1 and pairs[0][0] == shared
        assert int(c.page_table[1, 1]) == pairs[0][1] != shared
        assert c.refcount[shared] == 2  # owner + trie keep the original
        assert c.stat_cow == 1
        # exclusively-owned pages never COW again
        assert c.prepare_write(1, 11, 14) == []

    def test_refcount_zero_returns_to_free_list(self):
        c = PagedKVCache(2, 4, 8)
        toks = list(range(16))
        c.ensure_pos(0, 15)
        c.register_prefix(0, toks)
        c.share_prefix(1, toks)
        free0 = c.num_free
        assert c.free_slot(0) == 0  # trie + sharer still hold both pages
        assert c.free_slot(1) == 0  # trie still holds them
        assert c.num_free == free0
        assert c.clear_prefix_cache() == 2  # last reference: freed
        assert c.num_free == free0 + 2
        assert (c.refcount[1:] == 0).all()

    def test_exhaustion_reclaims_cold_trie_pages(self):
        """Under pool pressure, LRU trie-only pages are reclaimed
        instead of raising MemoryError."""
        c = PagedKVCache(2, 2, 4, num_pages=5, layout="naive")
        c.ensure_pos(0, 7)  # 2 pages
        c.register_prefix(0, list(range(8)))
        c.free_slot(0)  # pages survive via trie retention only
        assert c.num_free == 2 and c.prefix_pages() == 2
        c.ensure_pos(1, 7)  # needs 2 pages: free list has 2
        c.ensure_pos(0, 3)  # needs 1 more: must evict a trie leaf
        assert c.prefix_pages() == 1
        c.free_slot(0)
        c.free_slot(1)
        assert c.clear_prefix_cache() == 1
        assert c.num_free == c.num_pages - 1

    def _check_invariants(self, c):
        refs = np.zeros(c.num_pages, dtype=int)
        for s in range(c.num_slots):
            for lp in range(int(c.pages_used[s])):
                phys = int(c.page_table[s, lp])
                if phys != TRASH_PAGE:
                    refs[phys] += 1
        for node in c._iter_trie():
            refs[node.page] += 1
        np.testing.assert_array_equal(refs[1:], c.refcount[1:])
        for phys in c._free:
            assert refs[phys] == 0, f"page {phys} free but referenced"

    def test_cow_churn_invariants_across_seeds(self):
        """Interleaved admission-with-sharing, growth (COW on shared
        pages) and eviction keep refcounts exactly equal to the table +
        trie reference counts, across 10 seeds."""
        for seed in range(10):
            rng = np.random.default_rng(seed)
            B, MP, ps = 4, 4, 8
            c = PagedKVCache(B, MP, ps, num_pages=40)
            base = [int(t) for t in rng.integers(0, 50, size=MP * ps)]
            pos = np.zeros(B, dtype=int)
            active = np.zeros(B, dtype=bool)
            for _ in range(200):
                s = int(rng.integers(0, B))
                if not active[s]:
                    n = int(rng.integers(2, MP * ps))
                    toks = base[:n]
                    matched = c.share_prefix(s, toks)
                    c.ensure_pos(s, n - 1)
                    c.prepare_write(s, matched, n)
                    c.register_prefix(s, toks)
                    pos[s] = n
                    active[s] = True
                elif pos[s] < MP * ps - 1 and rng.random() < 0.8:
                    c.ensure_pos(s, int(pos[s]))
                    c.prepare_write(s, int(pos[s]), int(pos[s]) + 1)
                    pos[s] += 1
                else:
                    c.free_slot(s)
                    active[s] = False
                self._check_invariants(c)
            assert c.stat_shared > 0 and c.stat_cow > 0
            for s in range(B):
                c.free_slot(s)
            c.clear_prefix_cache()
            assert c.num_free == c.num_pages - 1

    def test_sharing_gather_runs_bounded(self):
        """COW placement goes through the curve layout, so a shared-
        prefix workload's decode gather stream stays within 2x the
        run count of the identical unshared workload."""

        def churn(share, seed):
            rng = np.random.default_rng(seed)
            B, MP, ps = 4, 8, 16
            c = PagedKVCache(B, MP, ps)
            base = [int(t) for t in rng.integers(0, 50, size=3 * ps)]
            pos = np.zeros(B, dtype=int)
            for s in range(B):
                n = 2 * ps + int(rng.integers(0, ps))
                toks = base[:n]
                matched = c.share_prefix(s, toks) if share else 0
                c.ensure_pos(s, n - 1)
                c.prepare_write(s, matched, n)
                if share:
                    c.register_prefix(s, toks)
                pos[s] = n
            for _ in range(200):
                s = int(rng.integers(0, B))
                if pos[s] >= MP * ps - 1:
                    continue
                c.ensure_pos(s, int(pos[s]))
                c.prepare_write(s, int(pos[s]), int(pos[s]) + 1)
                pos[s] += 1
            return c.gather_runs()

        shared = np.mean([churn(True, s) for s in range(5)])
        unshared = np.mean([churn(False, s) for s in range(5)])
        assert shared <= 2.0 * unshared, (shared, unshared)


# ---------------------------------------------------------------------------
# prefix sharing + compiled prefill: engine level
# ---------------------------------------------------------------------------

SHARED_BASE = [2, 7, 1, 8, 2, 8, 4, 5, 9, 0, 4, 5, 2, 3, 5, 6, 2, 6, 4, 3]


def _shared_prompts():
    """4 prompts over 2 slots sharing a 20-token prefix with long
    divergent tails (page_size=16: every donor registers 2 full pages,
    so later admissions hit page 0 exactly and page 1 partially at 4
    common tokens) — forces trie hits, partial-page COW on the first
    post-match write, and slot re-admission."""
    return [
        SHARED_BASE + [7] * 15,
        SHARED_BASE + [9] * 17,
        SHARED_BASE + [11] * 14,
        SHARED_BASE + [13] * 16,
    ]


class TestPrefillSharingEngine:
    @pytest.mark.parametrize("arch", [GQA, MLA])
    def test_64_step_rollout_both_features_on(self, arch):
        """Acceptance: compiled prefill + prefix sharing stay greedy-
        token-identical to dense over 64-step rollouts, GQA and MLA,
        flash and xla, across slot re-admission with shared pages."""
        cfg = get_reduced(arch, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = _shared_prompts()

        def run(**kw):
            eng = _engine(cfg, params, max_len=160, **kw)
            reqs = [eng.submit(list(p), max_new=64) for p in prompts]
            eng.run_until_done()
            assert all(len(r.out) == 64 for r in reqs)
            return [r.out for r in reqs], eng

        ref, _ = run(paged=False, attn_impl="xla")
        for attn in ("xla", "flash"):
            outs, eng = run(
                paged=True, attn_impl=attn, prefill="compiled",
                prefix_sharing=True,
            )
            assert outs == ref, f"{arch}/{attn} diverged from dense"
            assert eng.kv_pages.stat_shared > 0, "sharing never engaged"
            assert eng.kv_pages.stat_cow > 0, "COW never triggered"

    def test_chunked_with_sharing_token_identical(self):
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = _shared_prompts()

        def run(**kw):
            eng = _engine(cfg, params, max_len=160, **kw)
            reqs = [eng.submit(list(p), max_new=12) for p in prompts]
            eng.run_until_done()
            return [r.out for r in reqs]

        ref = run(paged=False, attn_impl="xla")
        got = run(paged=True, attn_impl="flash", prefill="chunked",
                  prefix_sharing=True)
        assert got == ref

    def test_compiled_prefill_cache_matches_chunked(self):
        """Compiled-forward and chunked prefill leave the same cache
        state (real pages; the trash page absorbs different garbage by
        design).  Cache-level like the chunked-chunk test — different
        programs may drift by ulps."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompt = list(range(1, 21))
        caches = []
        for mode in ("chunked", "compiled"):
            eng = _engine(cfg, params, paged=True, prefill=mode)
            eng.submit(prompt, max_new=4)
            eng._attach()
            caches.append(
                jax.tree.map(lambda x: np.asarray(x)[:, 1:], eng.cache)
            )
            assert eng.pos[0] == len(prompt) - 1
        for a, b in zip(jax.tree.leaves(caches[0]), jax.tree.leaves(caches[1])):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_shared_admission_allocates_fewer_pages(self):
        """Acceptance: admitting prompts with a common prefix allocates
        strictly fewer fresh pages with sharing on than off."""
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = _shared_prompts()

        def alloc(share):
            eng = _engine(cfg, params, paged=True, prefill="compiled",
                          prefix_sharing=share, max_len=160)
            for p in prompts:
                eng.submit(list(p), max_new=4)
            eng.run_until_done()
            return eng.kv_pages.stat_allocated

        assert alloc(True) < alloc(False)

    def test_ctor_validation(self):
        cfg = get_reduced(GQA, dtype="float32")
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="prefill"):
            _engine(cfg, params, paged=True, prefill="eager")
        with pytest.raises(ValueError, match="paged"):
            _engine(cfg, params, paged=False, prefill="compiled")
        with pytest.raises(ValueError, match="paged"):
            _engine(cfg, params, paged=False, prefix_sharing=True)
