"""Attention blocks: GQA (RoPE, optional QKV bias) and MLA (DeepSeek-V2).

Two execution paths each:
  * ``forward``  — full-sequence (training / prefill), optionally backed by
    the FGF jump-over Pallas flash kernel (cfg.use_hilbert_kernels);
  * ``decode``   — single-token step against a KV cache.  MLA keeps the
    paper-faithful *compressed* cache (c_kv ⊕ k_rope, 576 f.p. numbers per
    position instead of 2·H·Dh) and uses the absorbed-weight form.

Caches are functional: dicts of arrays + an int32 ``pos`` scalar array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import apply_rope, dense_init, init_rmsnorm, matrix_spec, rms_norm, specs_rmsnorm

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.attn_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def specs_gqa(cfg: ModelConfig):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.attn_head_dim
    s = {
        "wq": matrix_spec((d, h * dh), tp_dim=1),
        "wk": matrix_spec((d, hkv * dh), tp_dim=1),
        "wv": matrix_spec((d, hkv * dh), tp_dim=1),
        "wo": matrix_spec((h * dh, d), tp_dim=0),
    }
    if cfg.qkv_bias:
        s["bq"], s["bk"], s["bv"] = P("model"), P("model"), P("model")
    return s


def _qkv(params, x, cfg: ModelConfig):
    B, S, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.attn_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (
        q.reshape(B, S, h, dh),
        k.reshape(B, S, hkv, dh),
        v.reshape(B, S, hkv, dh),
    )


def _sdpa(q, k, v, *, causal: bool, kv_len_mask=None):
    """q: (B,Sq,H,Dh); k/v: (B,Sk,Hkv,Dh) with GQA grouping.
    Full-materialisation path (short sequences / decode)."""
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    q = q.reshape(B, Sq, Hkv, g, Dh)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(Dh)
    Sk = k.shape[1]
    if causal and Sq > 1:
        mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_len_mask is not None:  # (B, Sk) bool: valid cache entries
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _flash_fwd_scan(q, k, v, causal: bool, kv_chunk: int):
    """Online-softmax forward.  q: (B,Sq,Hkv,g,Dh) PRE-SCALED f32;
    k/v: (B,Sk,Hkv,Dh).  Returns (out f32, lse f32 (B,Sq,Hkv,g))."""
    B, Sq, Hkv, g, Dh = q.shape
    Sk = k.shape[1]
    n_chunks = Sk // kv_chunk
    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq, dtype=jnp.int32) + (Sk - Sq)

    def body(carry, inp):
        acc, m, l = carry
        kb, vb, c = inp
        scores = jnp.einsum("bqhgd,bkhd->bqhgk", q, kb.astype(jnp.float32))
        if causal:
            kv_pos = c * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32)
        )
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Sq, Hkv, g, Dh), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, g), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32)),
    )
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, kv_chunk: int):
    """Flash attention with recompute backward: O(Sq·kv_chunk) live score
    memory in BOTH passes — the XLA twin of the Pallas jump-over kernel
    (which additionally *skips* fully-masked tiles instead of masking).
    q: (B,Sq,Hkv,g,Dh) pre-scaled; k/v: (B,Sk,Hkv,Dh)."""
    out, _ = _flash_fwd_scan(q, k, v, causal, kv_chunk)
    return out


def _flash_fwd(q, k, v, causal, kv_chunk):
    out, lse = _flash_fwd_scan(q, k, v, causal, kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, kv_chunk, res, dout):
    q, k, v, out, lse = res  # q/out/lse f32; k/v input dtype
    B, Sq, Hkv, g, Dh = q.shape
    Sk = k.shape[1]
    n_chunks = Sk // kv_chunk
    dout = dout.astype(jnp.float32)
    delta = jnp.sum(dout * out, axis=-1)  # (B,Sq,Hkv,g)
    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq, dtype=jnp.int32) + (Sk - Sq)

    def body(dq, inp):
        kb, vb, c = inp
        scores = jnp.einsum("bqhgd,bkhd->bqhgk", q, kb.astype(jnp.float32))
        if causal:
            kv_pos = c * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
        p = jnp.exp(scores - lse[..., None])  # (B,Sq,Hkv,g,chunk)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dout, vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds, kb.astype(jnp.float32))
        dk_b = jnp.einsum("bqhgk,bqhgd->bkhd", ds, q)
        dv_b = jnp.einsum("bqhgk,bqhgd->bkhd", p, dout)
        return dq, (dk_b, dv_b)

    dq0 = jnp.zeros_like(q)
    dq, (dk_c, dv_c) = jax.lax.scan(
        body, dq0, (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32))
    )
    dk = dk_c.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Hkv, Dh).astype(k.dtype)
    dv = dv_c.transpose(1, 0, 2, 3, 4).reshape(B, Sk, Hkv, Dh).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _sdpa_blocked(q, k, v, *, causal: bool, kv_chunk: int):
    """(B,Sq,H,Dh)×(B,Sk,Hkv,Dh) GQA wrapper around the flash core."""
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qf = q.reshape(B, Sq, Hkv, g, Dh).astype(jnp.float32) / np.sqrt(Dh)
    out = _flash(qf, k, v, causal, kv_chunk)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _sdpa_auto(q, k, v, *, causal: bool, kv_chunk: int = 1024):
    Sk = k.shape[1]
    if Sk > kv_chunk and Sk % kv_chunk == 0:
        return _sdpa_blocked(q, k, v, causal=causal, kv_chunk=kv_chunk)
    return _sdpa(q, k, v, causal=causal)


def gqa_forward(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_hilbert_kernels:
        from repro.kernels import ops as kops

        rep = cfg.num_heads // cfg.num_kv_heads
        out = kops.attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=cfg.causal and not cfg.encoder_only,
        ).transpose(0, 2, 1, 3)
    else:
        out = _sdpa_auto(q, k, v, causal=cfg.causal and not cfg.encoder_only)
    return out.reshape(B, S, -1) @ params["wo"]


def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hkv, dh = cfg.num_kv_heads, cfg.attn_head_dim
    return {
        "k": jnp.zeros((batch, max_len, hkv, dh), dtype),
        "v": jnp.zeros((batch, max_len, hkv, dh), dtype),
    }


def gqa_cache_specs(cfg: ModelConfig, seq_axes=None, model_on_heads: bool = True):
    """batch → dp; "model" goes on kv heads when they divide the axis,
    otherwise on the sequence dim (flash-decode style partial-softmax
    partitioning — scores over a seq-sharded cache reduce with a small
    all-reduce, instead of replicating the cache ``model``-fold)."""
    if model_on_heads:
        spec = P(("pod", "data"), seq_axes, "model", None)
    else:
        seq = ("model",) if seq_axes is None else (
            tuple(seq_axes) if isinstance(seq_axes, tuple) else (seq_axes,)
        ) + ("model",)
        spec = P(("pod", "data"), seq, None, None)
    return {"k": spec, "v": spec}


def gqa_decode(params, x, cfg: ModelConfig, cache, pos):
    """x: (B, 1, d); pos: int32[B] per-slot positions (continuous
    batching: every batch slot may be at a different depth).
    Returns (out, cache)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    pos_arr = pos[:, None]
    q = apply_rope(q, pos_arr, cfg.rope_theta)
    k = apply_rope(k, pos_arr, cfg.rope_theta)
    rows = jnp.arange(B, dtype=jnp.int32)
    cache = {
        "k": cache["k"].at[rows, pos].set(k[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[rows, pos].set(v[:, 0].astype(cache["v"].dtype)),
    }
    Sk = cache["k"].shape[1]
    valid = jnp.arange(Sk, dtype=jnp.int32)[None] <= pos[:, None]
    out = _sdpa(q, cache["k"], cache["v"], causal=False, kv_len_mask=valid)
    return out.reshape(B, 1, -1) @ params["wo"], cache


# ---------------------------------------------------------------------------
# paged decode (GQA)
# ---------------------------------------------------------------------------
#
# The serving cache is a physical page pool (P, Hkv, page_size, D) shared
# by all slots, addressed through an int32[B, max_pages] page table
# (see repro.serve.kv_pages).  Physical page 0 is the reserved trash
# page: unallocated table entries point at it, and writes from masked
# (inactive) slots are *diverted* into it so the per-step scatter needs
# no branch and no post-hoc where-merge over the pool.  Gathers never
# branch either — the attention mask is positional (kv_pos <= pos), so
# whatever garbage the trash page holds is multiplied by exactly zero.

def _paged_write(pages, new, page_table, pos, write_mask):
    """Scatter one token per slot into the physical pool.

    pages: (P, Hkv, ps, D); new: (B, Hkv, D); pos: int32[B].  Slots with
    ``write_mask == False`` write to the trash page instead (scatter
    collisions inside page 0 are harmless — it is never attended)."""
    ps = pages.shape[2]
    B = pos.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    phys = page_table[rows, pos // ps]
    if write_mask is not None:
        phys = jnp.where(write_mask, phys, 0)
    return pages.at[phys, :, pos % ps].set(new.astype(pages.dtype))


def _paged_write_many(pages, new, page_table, pos0, write_mask):
    """Scatter T tokens per slot into the physical pool (the prefill
    twin of :func:`_paged_write`).

    pages: (P, Hkv, ps, D); new: (B, T, Hkv, D) with token i of slot b
    at absolute position ``pos0[b] + i``; write_mask: bool (B, T) —
    padded / inactive lanes are diverted to the trash page (their
    logical page index is also clamped so out-of-range pad positions
    never index past the table)."""
    ps = pages.shape[2]
    MP = page_table.shape[1]
    B, T = new.shape[:2]
    positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    lp = jnp.minimum(positions // ps, MP - 1)
    phys = page_table[jnp.arange(B, dtype=jnp.int32)[:, None], lp]
    phys = jnp.where(write_mask, phys, 0)
    return pages.at[phys, :, positions % ps].set(new.astype(pages.dtype))


def _gather_pages(pages, page_table):
    """XLA gather of every slot's pages into a (B, MP*ps, Hkv, D)
    sequence view — the reference path's twin of the flash kernels'
    page-by-page DMA."""
    B, MP = page_table.shape
    _, Hkv, ps, D = pages.shape
    g = pages[page_table]  # (B, MP, Hkv, ps, D)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, MP * ps, Hkv, D)


def _sdpa_prefix(q, k, v, mask):
    """Paged-prefill attention reference: q (B,T,H,Dh) over gathered
    pools k/v (B,S,Hkv,Dh) with a full (B,T,S) boolean mask (causal by
    absolute position — each query row's reduction is element-for-
    element the same as the chunked decode path's single-row
    ``_sdpa``)."""
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, T, Hkv, g, Dh)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(Dh)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, Dh).astype(q.dtype)


def gqa_init_pages(cfg: ModelConfig, num_pages: int, page_size: int, dtype):
    hkv, dh = cfg.num_kv_heads, cfg.attn_head_dim
    return {
        "k_pages": jnp.zeros((num_pages, hkv, page_size, dh), dtype),
        "v_pages": jnp.zeros((num_pages, hkv, page_size, dh), dtype),
    }


def gqa_decode_paged(params, x, cfg: ModelConfig, pools, pos, page_table, *,
                     write_mask=None, attn_impl: str = "flash"):
    """Single-token GQA decode against a paged cache.

    x: (B, 1, d); pos: int32[B]; page_table: int32[B, max_pages].
    attn_impl="flash" runs the grouped Pallas decode kernel natively on
    (B, Hkv, g) queries — no head expansion; "xla" gathers the pages
    and runs the retained ``_sdpa`` (the differential reference).
    Returns (out, pools)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    pos_arr = pos[:, None]
    q = apply_rope(q, pos_arr, cfg.rope_theta)
    k = apply_rope(k, pos_arr, cfg.rope_theta)
    pools = {
        "k_pages": _paged_write(pools["k_pages"], k[:, 0], page_table, pos, write_mask),
        "v_pages": _paged_write(pools["v_pages"], v[:, 0], page_table, pos, write_mask),
    }
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.attn_head_dim
    if attn_impl == "flash":
        from repro.kernels import ops as kops

        qg = q[:, 0].reshape(B, Hkv, H // Hkv, Dh)
        out = kops.attention_decode(
            qg, pools["k_pages"], pools["v_pages"], page_table, pos,
            sm_scale=1.0 / np.sqrt(Dh),
        )
        out = out.reshape(B, 1, H * Dh).astype(x.dtype)
    else:
        k_all = _gather_pages(pools["k_pages"], page_table)
        v_all = _gather_pages(pools["v_pages"], page_table)
        kv_len = k_all.shape[1]
        valid = jnp.arange(kv_len, dtype=jnp.int32)[None] <= pos[:, None]
        out = _sdpa(q, k_all, v_all, causal=False, kv_len_mask=valid)
        out = out.reshape(B, 1, -1)
    return out @ params["wo"], pools


def gqa_prefill_paged(params, x, cfg: ModelConfig, pools, pos0, n_new,
                      page_table, *, attn_impl: str = "flash", schedule=None):
    """Batched multi-token GQA prefill against a paged cache.

    x: (B, T, d) — T new prompt tokens per slot (token i at absolute
    position ``pos0[b] + i``; rows at i >= n_new[b] are padding).
    Split-phase: the cohort's K/V is scattered through the page table
    first (masked — pad and inactive lanes hit the trash page), then
    every new token attends causally over its slot's whole prefix in
    one dispatch.  ``schedule`` is the prefill page schedule (required
    for attn_impl="flash" under a trace).  Returns (out, pools)."""
    B, T, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    wm = jnp.arange(T, dtype=jnp.int32)[None] < n_new[:, None]
    pools = {
        "k_pages": _paged_write_many(pools["k_pages"], k, page_table, pos0, wm),
        "v_pages": _paged_write_many(pools["v_pages"], v, page_table, pos0, wm),
    }
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.attn_head_dim
    if attn_impl == "flash":
        from repro.kernels import ops as kops

        qg = q.reshape(B, T, Hkv, H // Hkv, Dh)
        out = kops.attention_prefill(
            qg, pools["k_pages"], pools["v_pages"], page_table, pos0,
            sm_scale=1.0 / np.sqrt(Dh), schedule=schedule,
        )
        out = out.reshape(B, T, H * Dh).astype(x.dtype)
    else:
        k_all = _gather_pages(pools["k_pages"], page_table)
        v_all = _gather_pages(pools["v_pages"], page_table)
        kv_len = k_all.shape[1]
        mask = (
            jnp.arange(kv_len, dtype=jnp.int32)[None, None]
            <= positions[:, :, None]
        )
        out = _sdpa_prefix(q, k_all, v_all, mask)
        out = out.reshape(B, T, -1)
    # Zero padding rows: q tiles past a slot's last schedule row are
    # never written by the flash kernel (uninitialised -> NaN), and a
    # NaN pad activation would reach the trash page, from where flash
    # decode's online softmax leaks it back through 0 * NaN.
    out = jnp.where(wm[:, :, None], out, 0.0)
    return out @ params["wo"], pools


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    d, h = cfg.d_model, cfg.num_heads
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": dense_init(ks[1], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "kv_norm": init_rmsnorm(cfg.kv_lora_rank, dtype),
        "wkv_b": dense_init(
            ks[2], cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype
        ),
        "wo": dense_init(ks[3], h * cfg.v_head_dim, d, dtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = init_rmsnorm(cfg.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[4], cfg.q_lora_rank, h * dqk, dtype)
    else:
        p["wq"] = dense_init(ks[5], d, h * dqk, dtype)
    return p


def specs_mla(cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    s = {
        "wkv_a": matrix_spec((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), tp_dim=None),
        "kv_norm": specs_rmsnorm(),
        "wkv_b": matrix_spec(
            (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), tp_dim=1
        ),
        "wo": matrix_spec((h * cfg.v_head_dim, d), tp_dim=0),
    }
    if cfg.q_lora_rank:
        s["wq_a"] = matrix_spec((d, cfg.q_lora_rank), tp_dim=None)
        s["q_norm"] = specs_rmsnorm()
        s["wq_b"] = matrix_spec((cfg.q_lora_rank, h * dqk), tp_dim=1)
    else:
        s["wq"] = matrix_spec((d, h * dqk), tp_dim=1)
    return s


def _mla_q(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    h = cfg.num_heads
    if cfg.q_lora_rank:
        cq = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
        q = cq @ params["wq_b"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, S, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(params, x, cfg: ModelConfig, positions):
    ckv_full = x @ params["wkv_a"]
    c_kv, k_rope = jnp.split(ckv_full, [cfg.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope  # (B,S,r), (B,S,dr)


def mla_forward(params, x, cfg: ModelConfig, positions, kv_chunk: int = 1024):
    """Training / prefill path: expand the latent into full K/V heads.
    Long sequences use the blockwise form — the latent is expanded one kv
    chunk at a time, so the (B,S,H,Dh) K/V tensors never materialise."""
    B, S, _ = x.shape
    h = cfg.num_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c_kv, k_rope = _mla_ckv(params, x, cfg, positions)
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    if S <= kv_chunk or S % kv_chunk:
        kv = (c_kv @ params["wkv_b"]).reshape(B, S, h, dn + dv)
        k_nope, v = jnp.split(kv, [dn], axis=-1)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
            + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
        ) * scale
        if cfg.causal:
            mask = jnp.tril(jnp.ones((S, S), dtype=bool))
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(x.dtype)
        return out.reshape(B, S, -1) @ params["wo"]

    # long-sequence path: expand the latent to per-head K/V in bf16 (the
    # head dim is model-sharded, so the expansion is device-local) and run
    # the flash core: O(S·chunk) score memory in BOTH passes (custom VJP).
    kv = (c_kv @ params["wkv_b"]).reshape(B, S, h, dn + dv)
    k_nope, v = jnp.split(kv, [dn], axis=-1)
    dr = cfg.qk_rope_head_dim
    k_full = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(k_rope[:, :, None, :], (B, S, h, dr)).astype(k_nope.dtype)],
        axis=-1,
    )  # (B,S,h,dn+dr)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,h,dn+dr)
    qf = (q_full.astype(jnp.float32) * scale)[:, :, :, None, :]  # g=1
    # pad V up to the K head dim so the flash core sees one head width
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
    out = _flash(qf, k_full, v_pad, cfg.causal, kv_chunk)
    out = out[:, :, :, 0, :dv].astype(x.dtype)
    return out.reshape(B, S, -1) @ params["wo"]


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_cache_specs(cfg: ModelConfig, seq_axes=None, model_on_heads: bool = True):
    # the compressed latent has no head dim — "model" always shards seq
    seq = ("model",) if seq_axes is None else (
        tuple(seq_axes) if isinstance(seq_axes, tuple) else (seq_axes,)
    ) + ("model",)
    return {
        "c_kv": P(("pod", "data"), seq, None),
        "k_rope": P(("pod", "data"), seq, None),
    }


def mla_decode(params, x, cfg: ModelConfig, cache, pos):
    """Absorbed-weight decode against the compressed cache (paper-faithful
    MLA: per-token cache is kv_lora_rank + qk_rope_head_dim numbers).
    pos: int32[B] per-slot positions."""
    B = x.shape[0]
    h = cfg.num_heads
    pos_arr = pos[:, None]
    q_nope, q_rope = _mla_q(params, x, cfg, pos_arr)  # (B,1,h,*)
    c_kv_new, k_rope_new = _mla_ckv(params, x, cfg, pos_arr)
    rows = jnp.arange(B, dtype=jnp.int32)
    cache = {
        "c_kv": cache["c_kv"].at[rows, pos].set(
            c_kv_new[:, 0].astype(cache["c_kv"].dtype)
        ),
        "k_rope": cache["k_rope"].at[rows, pos].set(
            k_rope_new[:, 0].astype(cache["k_rope"].dtype)
        ),
    }
    wkv_b = params["wkv_b"].reshape(
        cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    w_nope = wkv_b[:, :, : cfg.qk_nope_head_dim]  # (r, h, dn)
    w_v = wkv_b[:, :, cfg.qk_nope_head_dim :]  # (r, h, dv)
    # absorb: q' = q_nope @ w_nope^T  -> latent space
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32), w_nope.astype(jnp.float32))
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q_lat, cache["c_kv"].astype(jnp.float32))
        + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), cache["k_rope"].astype(jnp.float32))
    ) * scale
    Sk = cache["c_kv"].shape[1]
    valid = (jnp.arange(Sk, dtype=jnp.int32)[None] <= pos[:, None])[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", p, cache["c_kv"].astype(jnp.float32))
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, 1, -1) @ params["wo"], cache


# ---------------------------------------------------------------------------
# paged decode (MLA)
# ---------------------------------------------------------------------------

def mla_init_pages(cfg: ModelConfig, num_pages: int, page_size: int, dtype):
    """MLA pages the *compressed* latent: one pool leaf of width
    kv_lora_rank + qk_rope_head_dim per position (c_kv ⊕ k_rope), with a
    singleton kv-head axis so the pool shape matches the decode kernel's
    (P, Hkv, ps, D) contract."""
    w = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"kv_pages": jnp.zeros((num_pages, 1, page_size, w), dtype)}


def mla_decode_paged(params, x, cfg: ModelConfig, pools, pos, page_table, *,
                     write_mask=None, attn_impl: str = "flash"):
    """Absorbed-weight MLA decode against the paged compressed cache.

    MLA maps onto the grouped decode kernel with Hkv=1, g=num_heads:
    the latent pool (c_kv ⊕ k_rope) is passed as BOTH k_pages and
    v_pages — scores are q_lat·c_kv + q_rope·k_rope over the full
    r+dr width, the weighted value accumulates the same pool, and the
    context is sliced back to the first kv_lora_rank columns before the
    w_v expansion (the extra dr columns cost one slice, not a second
    pool).  Returns (out, pools)."""
    B = x.shape[0]
    h = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    pos_arr = pos[:, None]
    q_nope, q_rope = _mla_q(params, x, cfg, pos_arr)  # (B,1,h,*)
    c_kv_new, k_rope_new = _mla_ckv(params, x, cfg, pos_arr)
    new = jnp.concatenate([c_kv_new[:, 0], k_rope_new[:, 0]], axis=-1)
    pools = {
        "kv_pages": _paged_write(
            pools["kv_pages"], new[:, None, :], page_table, pos, write_mask
        )
    }
    wkv_b = params["wkv_b"].reshape(r, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
    w_nope = wkv_b[:, :, : cfg.qk_nope_head_dim]
    w_v = wkv_b[:, :, cfg.qk_nope_head_dim :]
    q_lat = jnp.einsum(
        "bqhd,rhd->bqhr", q_nope.astype(jnp.float32), w_nope.astype(jnp.float32)
    )
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if attn_impl == "flash":
        from repro.kernels import ops as kops

        q_full = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)
        qg = q_full[:, 0][:, None]  # (B, Hkv=1, g=h, r+dr)
        ctx = kops.attention_decode(
            qg, pools["kv_pages"], pools["kv_pages"], page_table, pos,
            sm_scale=float(scale),
        )
        ctx = ctx[:, 0, :, :r]  # (B, h, r): drop the k_rope columns
        out = jnp.einsum("bhr,rhd->bhd", ctx, w_v.astype(jnp.float32))
        out = out[:, None].astype(x.dtype)  # (B,1,h,dv)
    else:
        kv_all = _gather_pages(pools["kv_pages"], page_table)[:, :, 0]
        kv_len = kv_all.shape[1]
        c_all, kr_all = kv_all[..., :r], kv_all[..., r:]
        scores = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, c_all.astype(jnp.float32))
            + jnp.einsum(
                "bqhd,bkd->bhqk", q_rope.astype(jnp.float32), kr_all.astype(jnp.float32)
            )
        ) * scale
        valid = (jnp.arange(kv_len, dtype=jnp.int32)[None] <= pos[:, None])[:, None, None]
        scores = jnp.where(valid, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkr->bqhr", p, c_all.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, 1, -1) @ params["wo"], pools


def mla_prefill_paged(params, x, cfg: ModelConfig, pools, pos0, n_new,
                      page_table, *, attn_impl: str = "flash", schedule=None):
    """Batched multi-token absorbed-weight MLA prefill against the
    paged compressed cache (the prefill twin of
    :func:`mla_decode_paged`: Hkv=1, g=num_heads, the latent pool
    passed as both k and v, context sliced back to kv_lora_rank).
    Returns (out, pools)."""
    B, T, _ = x.shape
    h = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)  # (B,T,h,*)
    c_kv_new, k_rope_new = _mla_ckv(params, x, cfg, positions)
    new = jnp.concatenate([c_kv_new, k_rope_new], axis=-1)[:, :, None, :]
    wm = jnp.arange(T, dtype=jnp.int32)[None] < n_new[:, None]
    pools = {
        "kv_pages": _paged_write_many(
            pools["kv_pages"], new, page_table, pos0, wm
        )
    }
    wkv_b = params["wkv_b"].reshape(r, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
    w_nope = wkv_b[:, :, : cfg.qk_nope_head_dim]
    w_v = wkv_b[:, :, cfg.qk_nope_head_dim :]
    q_lat = jnp.einsum(
        "bqhd,rhd->bqhr", q_nope.astype(jnp.float32), w_nope.astype(jnp.float32)
    )
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if attn_impl == "flash":
        from repro.kernels import ops as kops

        q_full = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)
        qg = q_full[:, :, None]  # (B, T, Hkv=1, g=h, r+dr)
        ctx = kops.attention_prefill(
            qg, pools["kv_pages"], pools["kv_pages"], page_table, pos0,
            sm_scale=float(scale), schedule=schedule,
        )
        ctx = ctx[:, :, 0, :, :r]  # (B, T, h, r): drop the k_rope columns
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v.astype(jnp.float32))
        out = out.astype(x.dtype)
    else:
        kv_all = _gather_pages(pools["kv_pages"], page_table)[:, :, 0]
        kv_len = kv_all.shape[1]
        c_all, kr_all = kv_all[..., :r], kv_all[..., r:]
        scores = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, c_all.astype(jnp.float32))
            + jnp.einsum(
                "bqhd,bkd->bhqk", q_rope.astype(jnp.float32), kr_all.astype(jnp.float32)
            )
        ) * scale
        mask = (
            jnp.arange(kv_len, dtype=jnp.int32)[None, None]
            <= positions[:, :, None]
        )[:, None]  # (B, 1, T, S) over the head axis
        scores = jnp.where(mask, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkr->bqhr", p, c_all.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v.astype(jnp.float32)).astype(x.dtype)
    # Zero padding rows — same NaN containment as gqa_prefill_paged.
    out = jnp.where(wm[:, :, None, None], out, 0.0)
    return out.reshape(B, T, -1) @ params["wo"], pools
