"""ShapeDtypeStruct stand-ins + sharded step builders for the dry-run.

No device memory is ever allocated here: parameters, batches and caches
are ``jax.ShapeDtypeStruct`` trees produced with ``jax.eval_shape``; the
launcher lowers against them and compiles for the production mesh.

Dry-run stand-ins for the production mesh (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import zo
from repro.distributed import ctx, sharding
from repro.models import frontends, lm
from repro.models.config import ModelConfig


def param_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.eval_shape(lambda: lm.init_cache(cfg, batch, max_seq))


def input_specs(cfg: ModelConfig, shape) -> Dict[str, Any]:
    """Model inputs for one grid cell (see configs.shapes)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        batch = {"labels": jax.ShapeDtypeStruct((B, S), i32),
                 "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
        if frontends.uses_embeds(cfg):
            batch["embeds"] = jax.ShapeDtypeStruct(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype))
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        return {"batch": batch}
    if shape.kind == "prefill":
        if frontends.uses_embeds(cfg):
            return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   jnp.dtype(cfg.dtype))}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    # decode: one new token against a seq_len cache
    out = {"caches": cache_specs(cfg, B, S),
           "pos": jax.ShapeDtypeStruct((), i32)}
    if frontends.uses_embeds(cfg):
        out["embeds"] = jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                             jnp.dtype(cfg.dtype))
    else:
        out["token"] = jax.ShapeDtypeStruct((B, 1), i32)
    return out


def zo_variant(cfg: ModelConfig, variant: str) -> zo.ZOConfig:
    """faithful = the paper's MeZO-style LeZO (separate restore+update,
    uniform policy); optimized = beyond-paper (stratified policy, fused
    restore+update)."""
    n_drop = int(0.75 * cfg.num_layers)
    if variant == "faithful":
        return zo.ZOConfig(n_drop=n_drop, policy="uniform",
                           fused_update=False)
    if variant == "optimized":
        return zo.ZOConfig(n_drop=n_drop, policy="stratified",
                           fused_update=True)
    if variant == "mezo":
        return zo.ZOConfig(n_drop=0, policy="uniform", fused_update=False)
    raise ValueError(variant)


def build_train_step(cfg: ModelConfig, mesh, variant: str = "optimized"):
    """jit'd LeZO train step with explicit shardings, ready to lower."""
    ctx.set_mesh(mesh)
    zcfg = zo_variant(cfg, variant)
    spec = zo.build_spec(param_specs(cfg), lm.zo_group_fn)
    loss_fn = functools.partial(lm.lm_loss, cfg)
    step = zo.make_zo_step(loss_fn, spec, zcfg)

    pshapes = param_specs(cfg)
    p_shard = sharding.params_sharding(cfg, pshapes, mesh)
    scalar = NamedSharding(mesh, P())

    def wrapped(params, batch, step_idx, base_seed):
        return step(params, batch, step_idx, base_seed)

    def shard_fn(batch_specs):
        b_shard = sharding.batch_sharding(batch_specs, mesh)
        return jax.jit(
            wrapped,
            in_shardings=(p_shard, b_shard, scalar, scalar),
            out_shardings=(p_shard, None),
            donate_argnums=(0,),
        )
    return shard_fn, pshapes


def build_prefill_step(cfg: ModelConfig, mesh, max_seq: int):
    ctx.set_mesh(mesh)
    pshapes = param_specs(cfg)
    p_shard = sharding.params_sharding(cfg, pshapes, mesh)

    if frontends.uses_embeds(cfg):
        def prefill_fn(params, embeds):
            return lm.prefill(cfg, params, None, max_seq=max_seq,
                              embeds=embeds)
    else:
        def prefill_fn(params, tokens):
            return lm.prefill(cfg, params, tokens, max_seq=max_seq)

    def shard_fn(B):
        c_shard = sharding.cache_sharding(cache_specs(cfg, B, max_seq), mesh)
        logits_shard = NamedSharding(
            mesh, P(sharding.batch_axes(mesh) if B % _nbatch(mesh) == 0
                    else None, None))
        data_shard = NamedSharding(
            mesh, P(sharding.batch_axes(mesh) if B % _nbatch(mesh) == 0
                    else None, *([None, None] if frontends.uses_embeds(cfg)
                                 else [None])))
        return jax.jit(prefill_fn, in_shardings=(p_shard, data_shard),
                       out_shardings=(logits_shard, c_shard))
    return shard_fn, pshapes


def _nbatch(mesh):
    n = 1
    for a in sharding.batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def build_serve_step(cfg: ModelConfig, mesh, cache_len: int, batch: int):
    ctx.set_mesh(mesh)
    pshapes = param_specs(cfg)
    p_shard = sharding.params_sharding(cfg, pshapes, mesh)
    cshapes = cache_specs(cfg, batch, cache_len)
    c_shard = sharding.cache_sharding(cshapes, mesh)
    scalar = NamedSharding(mesh, P())
    B = batch
    tok_shard = NamedSharding(
        mesh, P(sharding.batch_axes(mesh) if B % _nbatch(mesh) == 0 else None,
                None))
    logits_shard = tok_shard

    if frontends.uses_embeds(cfg):
        emb_shard = NamedSharding(
            mesh, P(sharding.batch_axes(mesh) if B % _nbatch(mesh) == 0
                    else None, None, None))

        def serve_fn(params, caches, embeds, pos):
            return lm.serve_step(cfg, params, caches, None, pos,
                                 embeds=embeds)
        fn = jax.jit(serve_fn,
                     in_shardings=(p_shard, c_shard, emb_shard, scalar),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(1,))
    else:
        def serve_fn(params, caches, token, pos):
            return lm.serve_step(cfg, params, caches, token, pos)
        fn = jax.jit(serve_fn,
                     in_shardings=(p_shard, c_shard, tok_shard, scalar),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(1,))
    return fn, pshapes, cshapes
