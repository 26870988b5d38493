"""Mixture-of-Experts with sort-based capacity dispatch (EP over "model").

Top-k routing (OLMoE: 64e/top-8; DeepSeek-V2: 2 shared + 160 routed/top-6)
with the standard drop-on-overflow capacity discipline.  Dispatch is
sort-based (argsort by expert id → ranked slots → batched expert GEMMs on
an (E, C, d) buffer), which is jit-friendly and shards: the expert axis E
maps to the "model" mesh axis, so XLA lowers the scatter/gather pair into
the EP all-to-alls visible in the compiled HLO.

Beyond-paper hook: the dispatch *slot order* within each expert is a free
permutation — ``repro.core`` Hilbert keys over (expert, token-position)
can order slots so that the combine-side gather walks token positions
locality-preservingly.  Exposed as ``sort_tokens_by`` (default: plain).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import dense_init, matrix_spec


def init_moe(key, cfg: ModelConfig, dtype):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d)) / np.sqrt(f)).astype(dtype),
    }
    if cfg.num_shared_experts:
        from .layers import init_mlp

        p["shared"] = init_mlp(
            ks[4], d, cfg.num_shared_experts * f, "swiglu", dtype
        )
    return p


def specs_moe(cfg: ModelConfig):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    s = {
        "router": matrix_spec((d, E), tp_dim=None),
        "w_gate": P("model", "data", None),
        "w_up": P("model", "data", None),
        "w_down": P("model", None, "data"),
    }
    if cfg.num_shared_experts:
        from .layers import specs_mlp

        s["shared"] = specs_mlp(d, cfg.num_shared_experts * f, "swiglu")
    return s


def _router_aux(xt, router_w, cfg: ModelConfig):
    """Switch-style load-balance loss over the FULL expert set.

    Computed from the replicated router weights alone, so it lives
    OUTSIDE the shard_map in the EP path: the EP aux is then exactly the
    dense-path aux (one global token mean, not a pmean of per-shard
    estimates — the mean-of-products aux is nonlinear in the token mean),
    and the shard_map body has no reduction whose transpose would choke
    on the symbolic-zero cotangent aux gets whenever a loss uses only the
    block output (jax 0.4.x ``pmean(Zero)`` transpose failure).
    """
    E = cfg.num_experts
    logits = xt.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.top_k)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    return E * jnp.sum(me * ce)


def _dispatch_compute_combine(
    xt, router_w, w_gate, w_up, w_down, cfg: ModelConfig, e_offset, E_local: int,
    token_mask=None, lossless=False,
):
    """Core MoE math over ``E_local`` experts starting at ``e_offset``.

    Routing/top-k run over the FULL expert set (router is replicated);
    dispatch/GEMM/combine touch only the local experts — tokens routed
    elsewhere contribute zero here and are summed in by the model-axis
    psum of the EP wrapper.  With e_offset=0, E_local=E this is the plain
    single-device forward.  Returns out (T, d) f32.

    ``token_mask`` (bool (T,), optional) marks valid tokens: invalid
    tokens (prefill padding rows) are sorted past every expert segment,
    so they neither consume expert capacity nor contribute output —
    without it a cohort's pad rows can displace another slot's real
    tokens from a capacity-bounded expert.

    ``lossless`` sizes every expert buffer to hold all routed entries,
    so no token is ever dropped.  The serving paths require it: capacity
    ``cap = f(T)`` makes drop behaviour depend on the dispatch shape,
    and the engine's differential contract (chunked == compiled ==
    dense, greedy-token-identical) only holds when a token's expert
    output is independent of how many other tokens share its dispatch."""
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.top_k

    logits = xt.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)  # renormalise

    # ---- sort-based dispatch over the local experts ----------------------
    if lossless:
        cap = int(np.ceil(T * k / 8.0) * 8)  # every routed entry fits
    else:
        cap = int(np.ceil(T * k / E * cfg.capacity_factor / 8.0) * 8)
    e_flat = top_e.reshape(-1) - e_offset  # local expert ids (may be OOB)
    w_flat = top_w.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    local = (e_flat >= 0) & (e_flat < E_local)
    if token_mask is not None:
        local = local & token_mask[tok_flat]
    e_key = jnp.where(local, e_flat, E_local)  # non-local sorts to the end

    order = jnp.argsort(e_key, stable=True)
    e_sorted = e_key[order]
    counts = jnp.bincount(e_key, length=E_local + 1)
    seg_start = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * k, dtype=jnp.int32) - seg_start[e_sorted]
    keep = (rank < cap) & (e_sorted < E_local)
    slot = jnp.where(keep, e_sorted * cap + rank, E_local * cap)  # dump row

    buf = jnp.zeros((E_local * cap + 1, d), dtype=xt.dtype)
    buf = buf.at[slot].set(xt[tok_flat[order]])
    h = buf[: E_local * cap].reshape(E_local, cap, d)

    # ---- expert GEMMs ------------------------------------------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate))
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    y = jnp.einsum("ecf,efd->ecd", g * u, w_down)  # (E_local, C, d)

    # ---- combine -------------------------------------------------------------
    y_flat = jnp.concatenate([y.reshape(E_local * cap, d), jnp.zeros((1, d), y.dtype)])
    contrib = y_flat[slot] * (w_flat[order] * keep)[:, None].astype(y.dtype)
    out = jnp.zeros((T, d), dtype=jnp.float32)
    out = out.at[tok_flat[order]].add(contrib.astype(jnp.float32))
    return out


def moe_forward(params, x, cfg: ModelConfig, token_mask=None, lossless=False):
    """x: (B, S, d) -> (B, S, d), aux load-balance loss (f32 scalar).

    ``token_mask`` (bool (B, S), optional): valid-token mask forwarded to
    the dispatch — padding rows are kept out of expert capacity (see
    :func:`_dispatch_compute_combine`).  ``lossless`` disables capacity
    dropping entirely (the serving/decode setting).

    Dispatch backends:
      * host-local / no mesh: single-device sort-based dispatch;
      * mesh with a "model" axis: **shard_map expert parallelism** — tokens
        stay replicated across "model" (the 2d activation layout), each
        model rank dispatches ONLY its E/16 experts into a shard-local
        (E_local, C_local, d) buffer, and one bf16 psum of (T_local, d)
        combines — the same activation all-reduce a dense TP MLP pays.
        This replaces the GSPMD-opaque global scatter that replicated the
        dispatch buffer (148 GiB/dev → ~0.2 GiB; EXPERIMENTS §Perf cell 2).
    """
    B, S, d = x.shape
    E = cfg.num_experts

    from .sharding import _STATE

    mesh = _STATE["mesh"]
    use_ep = (
        mesh is not None
        and "model" in mesh.axis_names
        and E % dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 0
    )

    aux = _router_aux(x.reshape(B * S, d), params["router"], cfg)
    mask_flat = None if token_mask is None else token_mask.reshape(B * S)
    if not use_ep:
        out = _dispatch_compute_combine(
            x.reshape(B * S, d), params["router"], params["w_gate"],
            params["w_up"], params["w_down"], cfg, 0, E, mask_flat, lossless,
        )
    else:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        m_size = sizes["model"]
        E_local = E // m_size
        dp = _STATE["dp"]
        dp_nomodel = tuple(a for a in dp if a != "model")
        x_spec = P(dp_nomodel if dp_nomodel else None, None, None)

        mask_bs = (
            jnp.ones((B, S), dtype=bool) if mask_flat is None
            else mask_flat.reshape(B, S)
        )
        mask_spec = P(dp_nomodel if dp_nomodel else None, None)

        def body(xl, ml, router_w, w_gate, w_up, w_down):
            Bl = xl.shape[0]
            rank = jax.lax.axis_index("model")
            out = _dispatch_compute_combine(
                xl.reshape(-1, d), router_w, w_gate, w_up, w_down,
                cfg, rank * E_local, E_local, ml.reshape(-1), lossless,
            )
            out = jax.lax.psum(out.astype(x.dtype), "model")
            return out.reshape(Bl, -1, d)

        out_bsd = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                x_spec,
                mask_spec,
                P(None, None),
                P("model", None, None),
                P("model", None, None),
                P("model", None, None),
            ),
            out_specs=x_spec,
        )(x, mask_bs, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])
        out = out_bsd.reshape(B * S, d).astype(jnp.float32)

    out = out.astype(x.dtype)
    if cfg.num_shared_experts:
        from .layers import mlp

        out = out + mlp(x.reshape(B * S, d), params["shared"], "swiglu")
    return out.reshape(B, S, d), aux
