"""Pure step functions + abstract input specs for the launchers and compile tests.

``input_specs(cfg, shape)`` returns weak-type-correct ShapeDtypeStruct
stand-ins for every input of the step being lowered — no device
allocation, so 236B-parameter cells lower on a CPU host.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.shapes import SHAPES, ShapeSpec
from repro.models import (
    ModelConfig,
    cache_specs,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    param_specs,
)
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, lr: float = 3e-4, clip: float = 1.0,
                    param_shardings=None):
    lr_fn = cosine_schedule(lr, 100, 10_000)

    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True
        )(state["params"])
        if param_shardings is not None:
            # pin grads to the parameter layout straight out of backward:
            # the DP reduction lowers to a reduce-scatter onto the shards
            # instead of a full all-reduce (§Perf iteration 2: -50% bytes)
            grads = jax.lax.with_sharding_constraint(grads, param_shardings)
        grads, gnorm = clip_by_global_norm(grads, clip)
        new_params, new_opt = adamw_update(
            grads, state["opt"], state["params"], lr_fn(state["opt"].step)
        )
        return {"params": new_params, "opt": new_opt}, {
            "loss": loss, "grad_norm": gnorm,
        }

    return train_step


def make_prefill_step(cfg: ModelConfig):
    """Serving prefill: full-sequence forward, last-position logits."""

    def prefill_step(params, batch):
        logits, _ = forward(params, batch, cfg)
        return logits[:, -1]

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, tokens, cache, pos):
        return decode_step(params, tokens, cache, pos, cfg)

    return serve_step


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_state(cfg: ModelConfig):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw_init(params))
    return {"params": params, "opt": opt}


def abstract_batch(cfg: ModelConfig, batch: int, seq: int, with_labels: bool):
    out: dict[str, Any] = {}
    if cfg.embed_inputs:
        out["tokens"] = _sds((batch, seq), jnp.int32)
    else:
        out["embeds"] = _sds((batch, seq, cfg.d_model), jnp.float32)
    if with_labels:
        out["labels"] = _sds((batch, seq), jnp.int32)
    return out


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


def input_specs(cfg: ModelConfig, shape: ShapeSpec):
    """(args tuple of ShapeDtypeStruct pytrees) for the shape's mode."""
    B, S = shape.global_batch, shape.seq_len
    if shape.mode == "train":
        return (abstract_state(cfg), abstract_batch(cfg, B, S, True))
    if shape.mode == "prefill":
        return (
            abstract_state(cfg)["params"],
            abstract_batch(cfg, B, S, False),
        )
    if shape.mode == "decode":
        if cfg.embed_inputs:
            tok = _sds((B, 1), jnp.int32)
        else:
            tok = _sds((B, 1, cfg.d_model), jnp.float32)
        return (
            abstract_state(cfg)["params"],
            tok,
            abstract_cache(cfg, B, S),
            _sds((B,), jnp.int32),
        )
    raise ValueError(shape.mode)


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def _dp_axes(mesh: Mesh):
    """Batch axes under the active sharding policy: pure-FSDP has no
    tensor-parallel work for the 'model' axis, so the batch spreads over
    it too (otherwise model ranks duplicate compute)."""
    from repro.models.layers import get_sharding_policy

    names = ("pod", "data", "model") if get_sharding_policy() == "fsdp" \
        else ("pod", "data")
    return tuple(n for n in names if n in mesh.axis_names)


def resolve_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Adapt a logical PartitionSpec to a concrete (mesh, array shape):
    axes absent from the mesh are dropped; a dim that is not divisible by
    its axis-size product falls back to replication (e.g. vocab 50280 on
    16 model shards, or global_batch 1 on the dp axes)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries: list = []
    for dim, entry in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in sizes)
        total = 1
        for a in axes:
            total *= sizes[a]
        if not axes or shape[dim] % total != 0:
            entries.append(None)
        else:
            entries.append(axes if len(axes) > 1 else axes[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard_tree(specs, abstract, mesh: Mesh):
    """Tree of NamedShardings from logical specs + abstract array shapes."""
    return jax.tree.map(
        lambda sp, ab: NamedSharding(mesh, resolve_spec(sp, ab.shape, mesh)),
        specs,
        abstract,
        is_leaf=lambda x: isinstance(x, P),
    )


def state_shardings(cfg: ModelConfig, mesh: Mesh, abstract=None):
    from repro.optim import AdamWState

    abstract = abstract or abstract_state(cfg)
    pspecs = param_specs(cfg)
    opt_specs = AdamWState(step=P(), m=pspecs, v=pspecs)
    return {
        "params": shard_tree(pspecs, abstract["params"], mesh),
        "opt": shard_tree(opt_specs, abstract["opt"], mesh),
    }


def batch_specs(cfg: ModelConfig, with_labels: bool, mesh: Mesh = None):
    from repro.models.layers import get_sharding_policy

    dp = ("pod", "data", "model") if get_sharding_policy() == "fsdp" \
        else ("pod", "data")
    out: dict[str, Any] = {}
    if cfg.embed_inputs:
        out["tokens"] = P(dp, None)
    else:
        out["embeds"] = P(dp, None, None)
    if with_labels:
        out["labels"] = P(dp, None)
    return out


def _with_act_mesh(fn, mesh: Mesh):
    """Trace ``fn`` under the activation-sharding context (the model's
    per-block anchors read it at trace time)."""
    from repro.models.sharding import activation_mesh

    dp = _dp_axes(mesh)

    def wrapped(*args):
        with activation_mesh(mesh, dp):
            return fn(*args)

    return wrapped


def jit_for_cell(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh):
    """The jitted (not yet lowered) step for an (arch-cfg, shape, mesh)."""
    from repro.models.layers import get_sharding_policy

    dp = ("pod", "data", "model") if get_sharding_policy() == "fsdp" \
        else ("pod", "data")
    if shape.mode == "train":
        st, bt = input_specs(cfg, shape)
        st_sh = state_shardings(cfg, mesh, st)
        fn = _with_act_mesh(
            make_train_step(cfg, param_shardings=st_sh["params"]), mesh
        )
        in_sh = (st_sh, shard_tree(batch_specs(cfg, True), bt, mesh))
        return jax.jit(fn, in_shardings=in_sh, out_shardings=(st_sh, None),
                       donate_argnums=(0,))
    if shape.mode == "prefill":
        fn = _with_act_mesh(make_prefill_step(cfg), mesh)
        pt, bt = input_specs(cfg, shape)
        in_sh = (
            shard_tree(param_specs(cfg), pt, mesh),
            shard_tree(batch_specs(cfg, False), bt, mesh),
        )
        out_abs = jax.eval_shape(fn, pt, bt)
        out_sh = shard_tree(P(dp, "model"), out_abs, mesh)
        return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    if shape.mode == "decode":
        fn = _with_act_mesh(make_decode_step(cfg), mesh)
        pt, tok, cache_abs, pos = input_specs(cfg, shape)
        # batch=1 long-context: shard the cache sequence dim over "data"
        seq_axes = "data" if shape.global_batch == 1 else None
        model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        model_on_heads = (
            cfg.num_kv_heads > 0 and cfg.num_kv_heads % model_size == 0
        )
        cspecs = cache_specs(cfg, seq_axes=seq_axes, model_on_heads=model_on_heads)
        csh = shard_tree(cspecs, cache_abs, mesh)
        tok_spec = P(dp, None) if cfg.embed_inputs else P(dp, None, None)
        in_sh = (
            shard_tree(param_specs(cfg), pt, mesh),
            shard_tree(tok_spec, tok, mesh),
            csh,
            shard_tree(P(dp), pos, mesh),
        )
        logits_abs, _ = jax.eval_shape(fn, pt, tok, cache_abs, pos)
        out_sh = (shard_tree(P(dp, "model"), logits_abs, mesh), csh)
        return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(2,))
    raise ValueError(shape.mode)
