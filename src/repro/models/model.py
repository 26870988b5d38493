"""LMModel: embed → blocks → head, with train/prefill/decode entry points.

Public surface used by the trainer, server and tests:

  init_params(key, cfg)          -> params pytree
  param_specs(cfg)               -> matching PartitionSpec pytree
  forward(params, batch, cfg)    -> logits (B, S, V) f32
  loss_fn(params, batch, cfg)    -> (loss, metrics)
  init_cache(cfg, B, max_len)    -> decode cache pytree
  cache_specs(cfg, seq_axes)     -> matching PartitionSpec pytree
  decode_step(params, tok, cache, pos, cfg) -> (logits (B, V), cache)

Batches: {"tokens": int32 (B,S)} or {"embeds": (B,S,d)} for stub
frontends (audio/VLM per assignment), plus "labels" int32 (B,S).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import transformer as tfm
from .config import ModelConfig
from .layers import (
    embed,
    init_embed,
    init_rmsnorm,
    matrix_spec,
    rms_norm,
    specs_embed,
    specs_rmsnorm,
    unembed,
)


def init_params(key, cfg: ModelConfig):
    cfg.validate()
    dtype = cfg.params_dtype
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {
        "blocks": tfm.init_stack(ks[0], cfg, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.embed_inputs:
        p["embed"] = init_embed(ks[1], cfg.vocab_size, cfg.d_model, dtype)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        p["head"] = init_embed(ks[2], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.hybrid_attn_every:
        p["shared_attn"] = tfm.init_shared_attn(ks[3], cfg, dtype)
    return p


def param_specs(cfg: ModelConfig):
    s: dict[str, Any] = {
        "blocks": tfm.specs_stack(cfg),
        "final_norm": specs_rmsnorm(),
    }
    if cfg.embed_inputs:
        s["embed"] = specs_embed(cfg.vocab_size, cfg.d_model)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        s["head"] = specs_embed(cfg.vocab_size, cfg.d_model)
    if cfg.hybrid_attn_every:
        s["shared_attn"] = tfm.specs_shared_attn(cfg)
    return s


def _inputs(params, batch, cfg: ModelConfig):
    from .sharding import shard_batch

    if cfg.embed_inputs:
        x = embed(batch["tokens"], params["embed"])
        B, S = batch["tokens"].shape
    else:
        x = batch["embeds"].astype(cfg.params_dtype)
        B, S = x.shape[0], x.shape[1]
    x = shard_batch(x)  # anchor: (B→dp, S, d) activation layout
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def forward(params, batch, cfg: ModelConfig):
    """Full-sequence forward.  Returns (logits f32 (B,S,V), aux)."""
    x, positions = _inputs(params, batch, cfg)
    x, aux = tfm.stack_forward(
        params["blocks"], x, cfg, positions, shared_attn=params.get("shared_attn")
    )
    from .sharding import shard_batch

    x = shard_batch(rms_norm(x, params["final_norm"], cfg.norm_eps))
    head = params.get("head") or params["embed"]
    return unembed(x, head), aux


def loss_fn(params, batch, cfg: ModelConfig, aux_weight: float = 0.01):
    from .sharding import shard_logits

    logits, aux = forward(params, batch, cfg)
    logits = shard_logits(logits)  # (B→dp, S, V→model): CE stays sharded
    labels = batch["labels"]
    # one-hot CE (no gather over the sharded vocab dim): the label pick
    # is a masked sum that partitions cleanly over "model".
    logp = jax.nn.log_softmax(logits, axis=-1)
    vocab_ids = jnp.arange(cfg.vocab_size, dtype=labels.dtype)
    onehot = labels[..., None] == vocab_ids
    ll = jnp.sum(jnp.where(onehot, logp, 0.0), axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = cfg.params_dtype
    one = tfm.block_init_cache(cfg, batch, max_len, dtype)
    caches = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one
    )
    out = {"blocks": caches}
    if cfg.hybrid_attn_every:
        napp = (cfg.num_layers + cfg.hybrid_attn_every - 1) // cfg.hybrid_attn_every
        from .attention import gqa_init_cache

        sc = gqa_init_cache(cfg, batch, max_len, dtype)
        out["shared"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (napp,) + a.shape), sc
        )
    return out


def cache_specs(cfg: ModelConfig, seq_axes=None, model_on_heads: bool = True):
    one = tfm.block_cache_specs(cfg, seq_axes, model_on_heads)
    specs = jax.tree.map(lambda sp: P(*((None,) + tuple(sp))), one)
    out = {"blocks": specs}
    if cfg.hybrid_attn_every:
        from .attention import gqa_cache_specs

        sc = gqa_cache_specs(cfg, seq_axes, model_on_heads)
        out["shared"] = jax.tree.map(lambda sp: P(*((None,) + tuple(sp))), sc)
    return out


def decode_step(params, tokens, cache, pos, cfg: ModelConfig):
    """tokens: int32 (B, 1); pos: int32[B] per-slot positions (continuous
    batching).  Returns (logits (B, V) f32, new cache)."""
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (tokens.shape[0],))
    if cfg.embed_inputs:
        x = embed(tokens, params["embed"])
    else:
        x = tokens  # pre-embedded single-frame input (stub frontends)
    x, new_blocks, new_shared = tfm.stack_decode(
        params["blocks"], x, cfg, cache["blocks"], pos,
        shared_attn=params.get("shared_attn"),
        shared_caches=cache.get("shared"),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head") or params["embed"]
    logits = unembed(x, head)[:, 0]
    out_cache = {"blocks": new_blocks}
    if cfg.hybrid_attn_every:
        out_cache["shared"] = new_shared
    return logits, out_cache


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Paged decode cache: one physical pool per layer, one page table
    shared by all layers (managed host-side by serve.kv_pages).  Pool
    leaves are (L, num_pages, page_size, Hkv, D)."""
    dtype = cfg.params_dtype
    one = tfm.block_init_pages(cfg, num_pages, page_size, dtype)
    pools = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one
    )
    return {"blocks": pools}


def decode_step_paged(params, tokens, cache, pos, page_table, cfg: ModelConfig,
                      *, write_mask=None, attn_impl: str = "flash"):
    """Paged twin of :func:`decode_step`.  page_table: int32[B, max_pages]
    (entry 0 = trash page); write_mask: bool[B] or None — False slots
    divert their cache write to the trash page (inactive continuous-
    batching slots).  Returns (logits (B, V) f32, new cache)."""
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (tokens.shape[0],))
    if cfg.embed_inputs:
        x = embed(tokens, params["embed"])
    else:
        x = tokens
    x, new_pools = tfm.stack_decode_paged(
        params["blocks"], x, cfg, cache["blocks"], pos, page_table,
        write_mask=write_mask, attn_impl=attn_impl,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head") or params["embed"]
    logits = unembed(x, head)[:, 0]
    return logits, {"blocks": new_pools}


def prefill_paged(params, tokens, cache, pos0, n_new, page_table,
                  cfg: ModelConfig, *, attn_impl: str = "flash",
                  schedule=None):
    """Compiled-forward batched prefill against the paged cache.

    tokens: int32 (B, T) — up to T new prompt tokens per slot (token i
    at absolute position ``pos0[b] + i``, zero-padded past ``n_new[b]``;
    slots with n_new == 0 ride along untouched).  Writes every new
    token's K/V through the shared page table and returns the updated
    cache — O(prompt) total flops per slot, versus the chunked
    masked-decode walk's O(prompt²).  Logits are not computed: the
    engine feeds the prompt's last token to the first decode step, the
    same contract as chunked prefill.  ``schedule`` is the prefill page
    schedule device table (required for attn_impl="flash"; ignored for
    "xla")."""
    if cfg.embed_inputs:
        x = embed(tokens, params["embed"])
    else:
        x = tokens
    _, new_pools = tfm.stack_prefill_paged(
        params["blocks"], x, cfg, cache["blocks"], pos0, n_new, page_table,
        attn_impl=attn_impl, schedule=schedule,
    )
    return {"blocks": new_pools}


def count_params(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top_k + shared experts only)."""
    full = param_count_analytic(cfg)
    if cfg.block_kind != "moe":
        return full
    routed_per_layer = 3 * cfg.d_model * cfg.d_ff_expert
    inactive = (cfg.num_experts - cfg.top_k) * routed_per_layer * cfg.num_layers
    return full - inactive


def param_count_analytic(cfg: ModelConfig) -> int:
    """Closed-form parameter count (no allocation) for roofline math."""
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    total = 0
    if cfg.embed_inputs:
        total += V * d
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        total += V * d
    total += d  # final norm
    if cfg.block_kind == "mamba2":
        di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        per = d + d * (2 * di + 2 * n + h) + cfg.ssm_conv_width * (di + 2 * n) \
            + (di + 2 * n) + 3 * h + di + di * d
        total += L * per
    else:
        dh = cfg.attn_head_dim
        if cfg.is_mla:
            dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            attn_p = d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.kv_lora_rank
            attn_p += cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            attn_p += cfg.num_heads * cfg.v_head_dim * d
            if cfg.q_lora_rank:
                attn_p += d * cfg.q_lora_rank + cfg.q_lora_rank + cfg.q_lora_rank * cfg.num_heads * dqk
            else:
                attn_p += d * cfg.num_heads * dqk
        else:
            attn_p = d * cfg.num_heads * dh + 2 * d * cfg.num_kv_heads * dh \
                + cfg.num_heads * dh * d
            if cfg.qkv_bias:
                attn_p += (cfg.num_heads + 2 * cfg.num_kv_heads) * dh
        if cfg.block_kind == "moe":
            ffn_p = d * cfg.num_experts  # router
            ffn_p += cfg.num_experts * 3 * d * cfg.d_ff_expert
            if cfg.num_shared_experts:
                ffn_p += 3 * d * cfg.num_shared_experts * cfg.d_ff_expert
        else:
            n_mat = 3 if cfg.mlp_act == "swiglu" else 2
            ffn_p = n_mat * d * cfg.d_ff
        total += L * (attn_p + ffn_p + 2 * d)
    if cfg.hybrid_attn_every:
        dh = cfg.attn_head_dim
        total += d + d * cfg.num_heads * dh + 2 * d * cfg.num_kv_heads * dh + cfg.num_heads * dh * d
        if cfg.d_ff:
            n_mat = 3 if cfg.mlp_act == "swiglu" else 2
            total += d + n_mat * d * cfg.d_ff
    return total
