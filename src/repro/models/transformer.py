"""Block definitions and the scanned layer stack.

Homogeneous stacks (all dense/moe/mamba2 archs) are lax.scan'd over
parameters stacked on a leading layer axis — compile size is O(1) in
depth, which matters at 60 layers × MoE.  The hybrid (Zamba2) pattern runs
the mamba scan in segments with the *shared* attention block applied
between segments (weight reuse is the Zamba2 design).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import init_mlp, init_rmsnorm, mlp, rms_norm, specs_mlp, specs_rmsnorm


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype):
    kind = cfg.block_kind
    ks = jax.random.split(key, 3)
    p: dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if kind == "mamba2":
        p["mixer"] = ssm_mod.init_mamba2(ks[0], cfg, dtype)
        return p
    p["attn"] = (
        attn.init_mla(ks[0], cfg, dtype) if cfg.is_mla else attn.init_gqa(ks[0], cfg, dtype)
    )
    p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
    if kind == "moe":
        p["ffn"] = moe_mod.init_moe(ks[1], cfg, dtype)
    else:
        p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def specs_block(cfg: ModelConfig):
    kind = cfg.block_kind
    s: dict[str, Any] = {"norm1": specs_rmsnorm()}
    if kind == "mamba2":
        s["mixer"] = ssm_mod.specs_mamba2(cfg)
        return s
    s["attn"] = attn.specs_mla(cfg) if cfg.is_mla else attn.specs_gqa(cfg)
    s["norm2"] = specs_rmsnorm()
    if kind == "moe":
        s["ffn"] = moe_mod.specs_moe(cfg)
    else:
        s["ffn"] = specs_mlp(cfg.d_model, cfg.d_ff, cfg.mlp_act)
    return s


def block_forward(params, x, cfg: ModelConfig, positions):
    """Returns (x, aux)."""
    from .sharding import shard_batch

    x = shard_batch(x)  # per-block activation anchor (B→dp, S, d)
    aux = jnp.zeros((), jnp.float32)
    kind = cfg.block_kind
    if kind == "mamba2":
        x = x + ssm_mod.mamba2_forward(params["mixer"], rms_norm(x, params["norm1"], cfg.norm_eps), cfg)
        return x, aux
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        x = x + attn.mla_forward(params["attn"], h, cfg, positions)
    else:
        x = x + attn.gqa_forward(params["attn"], h, cfg, positions)
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    if kind == "moe":
        y, aux = moe_mod.moe_forward(params["ffn"], h, cfg)
        x = x + y
    else:
        x = x + mlp(h, params["ffn"], cfg.mlp_act)
    return x, aux


def block_decode(params, x, cfg: ModelConfig, cache, pos):
    """Single-token step.  Returns (x, new_cache)."""
    kind = cfg.block_kind
    if kind == "mamba2":
        y, cache = ssm_mod.mamba2_decode(
            params["mixer"], rms_norm(x, params["norm1"], cfg.norm_eps), cfg, cache
        )
        return x + y, cache
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        y, cache = attn.mla_decode(params["attn"], h, cfg, cache, pos)
    else:
        y, cache = attn.gqa_decode(params["attn"], h, cfg, cache, pos)
    x = x + y
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    if kind == "moe":
        # lossless: serving dispatches come in many shapes (decode tick,
        # chunked prefill, compiled-forward prefill) and the engine's
        # differential contract needs shape-independent expert outputs
        y, _ = moe_mod.moe_forward(params["ffn"], h, cfg, lossless=True)
        x = x + y
    else:
        x = x + mlp(h, params["ffn"], cfg.mlp_act)
    return x, cache


def block_decode_paged(params, x, cfg: ModelConfig, pools, pos, page_table, *,
                       write_mask=None, attn_impl: str = "flash"):
    """Single-token step against a paged KV pool.  Returns (x, pools).

    Only pure attention stacks page — mamba2/hybrid carry O(1) recurrent
    state per slot, so there is nothing to page (the dense decode path
    remains the serving route for those archs)."""
    kind = cfg.block_kind
    if kind == "mamba2":
        raise NotImplementedError("recurrent blocks have no paged KV cache")
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        y, pools = attn.mla_decode_paged(
            params["attn"], h, cfg, pools, pos, page_table,
            write_mask=write_mask, attn_impl=attn_impl,
        )
    else:
        y, pools = attn.gqa_decode_paged(
            params["attn"], h, cfg, pools, pos, page_table,
            write_mask=write_mask, attn_impl=attn_impl,
        )
    x = x + y
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    if kind == "moe":
        y, _ = moe_mod.moe_forward(params["ffn"], h, cfg, lossless=True)
        x = x + y
    else:
        x = x + mlp(h, params["ffn"], cfg.mlp_act)
    return x, pools


def block_prefill_paged(params, x, cfg: ModelConfig, pools, pos0, n_new,
                        page_table, *, attn_impl: str = "flash",
                        schedule=None):
    """Batched multi-token prefill step against a paged KV pool: every
    new prompt token of every slot in one dispatch.  Returns (x, pools)."""
    kind = cfg.block_kind
    if kind == "mamba2":
        raise NotImplementedError("recurrent blocks have no paged KV cache")
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if cfg.is_mla:
        y, pools = attn.mla_prefill_paged(
            params["attn"], h, cfg, pools, pos0, n_new, page_table,
            attn_impl=attn_impl, schedule=schedule,
        )
    else:
        y, pools = attn.gqa_prefill_paged(
            params["attn"], h, cfg, pools, pos0, n_new, page_table,
            attn_impl=attn_impl, schedule=schedule,
        )
    x = x + y
    h = rms_norm(x, params["norm2"], cfg.norm_eps)
    if kind == "moe":
        # Padding rows (beyond each slot's n_new) carry garbage
        # activations; without the mask they are routed and can displace
        # another slot's REAL tokens from a capacity-bounded expert.
        T = x.shape[1]
        wm = jnp.arange(T, dtype=jnp.int32)[None] < n_new[:, None]
        y, _ = moe_mod.moe_forward(
            params["ffn"], h, cfg, token_mask=wm, lossless=True
        )
        x = x + y
    else:
        x = x + mlp(h, params["ffn"], cfg.mlp_act)
    return x, pools


def block_init_pages(cfg: ModelConfig, num_pages: int, page_size: int, dtype):
    if cfg.block_kind == "mamba2" or cfg.hybrid_attn_every:
        raise ValueError("paged KV serving requires a pure attention stack")
    if cfg.is_mla:
        return attn.mla_init_pages(cfg, num_pages, page_size, dtype)
    return attn.gqa_init_pages(cfg, num_pages, page_size, dtype)


def block_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.block_kind == "mamba2":
        return ssm_mod.mamba2_init_cache(cfg, batch, dtype)
    if cfg.is_mla:
        return attn.mla_init_cache(cfg, batch, max_len, dtype)
    return attn.gqa_init_cache(cfg, batch, max_len, dtype)


def block_cache_specs(cfg: ModelConfig, seq_axes=None, model_on_heads: bool = True):
    if cfg.block_kind == "mamba2":
        return ssm_mod.mamba2_cache_specs(cfg)
    if cfg.is_mla:
        return attn.mla_cache_specs(cfg, seq_axes, model_on_heads)
    return attn.gqa_cache_specs(cfg, seq_axes, model_on_heads)


# ---------------------------------------------------------------------------
# stacked layers
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, dtype):
    keys = jax.random.split(key, cfg.num_layers)
    return jax.vmap(lambda k: init_block(k, cfg, dtype))(keys)


def specs_stack(cfg: ModelConfig):
    """Block specs with the leading (scanned) layer axis prepended."""
    one = specs_block(cfg)
    return jax.tree.map(lambda sp: P(*((None,) + tuple(sp))), one)


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def stack_forward(stacked, x, cfg: ModelConfig, positions, shared_attn=None):
    """Run all layers.  Returns (x, total_aux).

    hybrid (Zamba2): shared_attn params are applied after every
    ``hybrid_attn_every`` mamba layers (same weights each application).
    """
    body = _maybe_remat(
        lambda p, x: block_forward(p, x, cfg, positions), cfg
    )

    def scan_fn(carry, layer_params):
        x, aux = carry
        x, a = body(layer_params, x)
        return (x, aux + a), None

    if not cfg.hybrid_attn_every:
        (x, aux), _ = jax.lax.scan(
            scan_fn, (x, jnp.zeros((), jnp.float32)), stacked
        )
        return x, aux

    # hybrid: segmented scan with shared attention between segments
    every = cfg.hybrid_attn_every
    L = cfg.num_layers
    aux = jnp.zeros((), jnp.float32)
    assert shared_attn is not None
    n_seg = (L + every - 1) // every
    for s in range(n_seg):
        lo, hi = s * every, min((s + 1) * every, L)
        seg = jax.tree.map(lambda a: a[lo:hi], stacked)
        (x, aux), _ = jax.lax.scan(scan_fn, (x, aux), seg)
        h = rms_norm(x, shared_attn["norm"], cfg.norm_eps)
        x = x + attn.gqa_forward(shared_attn["attn"], h, cfg, positions)
        x = _shared_block_tail(shared_attn, x, cfg)
    return x, aux


def stack_decode(stacked, x, cfg: ModelConfig, caches, pos, shared_attn=None,
                 shared_caches=None):
    """Single-token decode through all layers.  Returns (x, caches, shared)."""

    def scan_fn(x, inp):
        layer_params, cache = inp
        x, new_cache = block_decode(layer_params, x, cfg, cache, pos)
        return x, new_cache

    if not cfg.hybrid_attn_every:
        x, new_caches = jax.lax.scan(scan_fn, x, (stacked, caches))
        return x, new_caches, shared_caches

    every = cfg.hybrid_attn_every
    L = cfg.num_layers
    n_seg = (L + every - 1) // every
    new_parts = []
    new_shared = []
    for s in range(n_seg):
        lo, hi = s * every, min((s + 1) * every, L)
        seg_p = jax.tree.map(lambda a: a[lo:hi], stacked)
        seg_c = jax.tree.map(lambda a: a[lo:hi], caches)
        x, seg_c_new = jax.lax.scan(scan_fn, x, (seg_p, seg_c))
        new_parts.append(seg_c_new)
        h = rms_norm(x, shared_attn["norm"], cfg.norm_eps)
        sc = jax.tree.map(lambda a: a[s], shared_caches)
        y, sc_new = attn.gqa_decode(shared_attn["attn"], h, cfg, sc, pos)
        x = x + y
        x = _shared_block_tail(shared_attn, x, cfg)
        new_shared.append(sc_new)
    caches_out = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_parts)
    shared_out = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_shared)
    return x, caches_out, shared_out


def stack_decode_paged(stacked, x, cfg: ModelConfig, pools, pos, page_table, *,
                       write_mask=None, attn_impl: str = "flash"):
    """Single-token paged decode through all layers.  The page table is
    shared by every layer (one logical→physical map, L pools).
    Returns (x, pools)."""
    if cfg.hybrid_attn_every:
        raise ValueError("paged KV serving requires a pure attention stack")

    def scan_fn(x, inp):
        layer_params, pool = inp
        x, new_pool = block_decode_paged(
            layer_params, x, cfg, pool, pos, page_table,
            write_mask=write_mask, attn_impl=attn_impl,
        )
        return x, new_pool

    x, new_pools = jax.lax.scan(scan_fn, x, (stacked, pools))
    return x, new_pools


def stack_prefill_paged(stacked, x, cfg: ModelConfig, pools, pos0, n_new,
                        page_table, *, attn_impl: str = "flash",
                        schedule=None):
    """Batched paged prefill through all layers (the compiled-forward
    admission path: one scan over layers, each layer one scatter + one
    whole-cohort attention dispatch).  Returns (x, pools)."""
    if cfg.hybrid_attn_every:
        raise ValueError("paged KV serving requires a pure attention stack")

    def scan_fn(x, inp):
        layer_params, pool = inp
        x, new_pool = block_prefill_paged(
            layer_params, x, cfg, pool, pos0, n_new, page_table,
            attn_impl=attn_impl, schedule=schedule,
        )
        return x, new_pool

    x, new_pools = jax.lax.scan(scan_fn, x, (stacked, pools))
    return x, new_pools


def init_shared_attn(key, cfg: ModelConfig, dtype):
    """Zamba2-style shared transformer block (attention + MLP), applied
    with the same weights after every ``hybrid_attn_every`` mamba layers."""
    k1, k2 = jax.random.split(key)
    p = {
        "norm": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn.init_gqa(k1, cfg, dtype),
    }
    if cfg.d_ff:
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def specs_shared_attn(cfg: ModelConfig):
    s = {"norm": specs_rmsnorm(), "attn": attn.specs_gqa(cfg)}
    if cfg.d_ff:
        s["norm2"] = specs_rmsnorm()
        s["mlp"] = specs_mlp(cfg.d_model, cfg.d_ff, cfg.mlp_act)
    return s


def _shared_block_tail(shared_attn, x, cfg: ModelConfig):
    if "mlp" in shared_attn:
        h = rms_norm(x, shared_attn["norm2"], cfg.norm_eps)
        x = x + mlp(h, shared_attn["mlp"], cfg.mlp_act)
    return x
