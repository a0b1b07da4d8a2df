"""Mixture-of-Experts FFN with drop-free grouped-matmul dispatch.

Routing is DeepSeek/Granite-style: softmax over experts, greedy top-k,
renormalized over the k picks where ``cfg.norm_topk_prob`` says so (the
published ``norm_topk_prob``; DeepSeek-V2-Lite's is false).  Dispatch
never drops an assignment: a data shard's b*S*k token-expert assignments
are sorted by expert, each expert's FFN runs over its contiguous run of
sorted rows as one grouped matmul with dynamic group sizes
(``kernels.gmm.grouped_matmul``: the Pallas megablox kernel on a TPU,
``lax.ragged_dot`` elsewhere), and the outputs are weighted and
scatter-added back to their tokens.  Every shape is static (b*S*k rows); only the
split of the rows among experts moves with the routing.

The three parts are named scopes of the compiled program (``obs``):
``moe_dispatch`` (router, top-k, sort, gather), ``moe_experts`` (the
grouped matmuls and their activation) and ``moe_combine`` (weighting
and scatter-add).

Sharding note (see distributed/sharding.py): expert weights are sharded
over the *d_ff* axis (tensor parallelism inside every expert) rather than
over the expert axis, and the dispatch runs on each data shard's own
rows (``ctx.per_batch_shard``), so router and dispatch stay device-local
(no all-to-all); the only collective is the usual TP reduce of the FFN
output.  For ZO fine-tuning the TP layout wins because perturbation
touches all experts uniformly and the dispatch buffers never cross
devices.

Model stack / zoo (DESIGN.md §8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.kernels import gmm as kgmm
from repro.models import layers
from repro.obs import trace as obs

F32 = jnp.float32


def moe_params(cfg, key):
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "norm": layers.norm_params(cfg, D),
        "router": jax.random.normal(ks[0], (D, E), jnp.float32) * D ** -0.5,
        "we_g": jax.random.normal(ks[1], (E, D, F), dt) * D ** -0.5,
        "we_u": jax.random.normal(ks[2], (E, D, F), dt) * D ** -0.5,
        "we_d": jax.random.normal(ks[3], (E, F, D), dt) * F ** -0.5,
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        kss = jax.random.split(ks[4], 3)
        p["ws_g"] = jax.random.normal(kss[0], (D, Fs), dt) * D ** -0.5
        p["ws_u"] = jax.random.normal(kss[1], (D, Fs), dt) * D ** -0.5
        p["ws_d"] = jax.random.normal(kss[2], (Fs, D), dt) * Fs ** -0.5
    return p


def moe_fwd(cfg, p, x):
    """x: (B, S, D) -> (y, aux): every token's k assignments computed,
    none dropped, whatever the load on an expert.  Routing and dispatch
    run per data shard (``ctx.per_batch_shard``): the sort, gather and
    scatter-add only ever move rows of the shard that holds them."""
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.top_k
    tr = obs.get_tracer()
    if tr.enabled and not obs.tracing():
        tr.count(obs.CTR_MOE_ROUTED, B * S * k)
    h = ctx.constrain(layers.apply_norm(cfg, p["norm"], x),
                      "batch", None, None)                     # (B, S, D)
    y, me, ce = ctx.per_batch_shard(
        lambda h, *w: _routed(cfg, h, *w), h, p["router"], p["we_g"],
        p["we_u"], p["we_d"])
    y = ctx.constrain(y, "batch", None, None)
    # Switch-style load-balance loss over the whole batch
    aux = E * jnp.sum(jnp.mean(me, axis=0) * jnp.mean(ce, axis=0))

    if cfg.n_shared_experts:
        sg = jax.nn.silu((h @ p["ws_g"]).astype(F32))
        su = (h @ p["ws_u"]).astype(F32)
        y = y + jnp.einsum("bsf,fd->bsd", (sg * su).astype(x.dtype),
                           p["ws_d"], preferred_element_type=F32)

    return y.astype(x.dtype), aux


def _routed(cfg, h, router, we_g, we_u, we_d):
    """The routed experts on the rows of h (b, S, D) -> (y (b, S, D) f32,
    each row's mean router probabilities (b, E) and share of the
    assignments (b, E))."""
    b, S, D = h.shape
    E, k = cfg.n_experts, cfg.top_k
    T = b * S
    with obs.stage(obs.MOE_DISPATCH):
        logits = jnp.einsum("bsd,de->bse", h.astype(F32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)                 # (b, S, k)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=1)
        ce = jax.vmap(lambda e: jnp.zeros((E,), F32).at[e].add(1.0))(
            top_e.reshape(b, S * k)) / (S * k)
        e_flat = top_e.reshape(T * k)
        order = jnp.argsort(e_flat)                  # stable: token order
        tok = order // k                             # kept within an expert
        sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)
        xs = h.reshape(T, D)[tok]                              # (T*k, D)

    with obs.stage(obs.MOE_EXPERTS):
        gmm = lambda a, w: kgmm.grouped_matmul(a, w, sizes)
        a = (jax.nn.silu(gmm(xs, we_g)) * gmm(xs, we_u)).astype(h.dtype)
        o = gmm(a, we_d)                                       # (T*k, D)

    with obs.stage(obs.MOE_COMBINE):
        w_s = top_w.reshape(T * k)[order]
        y = jnp.zeros((T, D), F32).at[tok].add(o * w_s[:, None])
    return y.reshape(b, S, D), me, ce
