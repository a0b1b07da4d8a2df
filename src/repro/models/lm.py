"""Decoder-only LM assembly: init, train loss, prefill, decode.

Parameters live in a dict tree::

    {"embed": {"tok": (V, D) [, "pos": (max_seq, D)]},
     "final_norm": {...},
     ["head": {"w": (D, V)}]                      # absent when tied
     "stages": {"s0": {"b0": {...}, "b1": {...}}, ...}}

where every leaf under ``stages/s{i}/b{j}`` is stacked over that stage's
``repeat`` on axis 0.  Execution is a ``lax.scan`` over repeat per stage
(compile-time O(1) in depth — critical for 62-layer models on a
512-device mesh); each scan body runs the stage's block *pattern* in
order, so heterogeneous interleaves (jamba's mamba/attn, xlstm's
mlstm/slstm) execute in their true layer order.

LeZO integration: ``zo_group_fn`` labels each stages/ leaf with its
(stage, pattern-position) group; embeddings / head / final norm are
always-perturbed (the paper never drops them — and Fig. 3 shows dropping
everything *but* them collapses).

The LM loss is a chunked cross-entropy (scan over sequence chunks): the
(B, S, V) logits tensor never materializes — at 152k vocab x 4k seq that
is the difference between fitting a v5e and a 20 GiB OOM.

Model stack (DESIGN.md §8); paged serving mode: DESIGN.md §12.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.fused import ref as fused_ref
from repro.models import layers, moe, ssm
from repro.models.config import BlockCfg, ModelConfig
from repro.obs import trace as obs

F32 = jnp.float32
CE_CHUNK = 512


# ------------------------------------------------------------------ init
def _block_params(cfg: ModelConfig, b: BlockCfg, key):
    kmix, kffn = jax.random.split(key)
    if b.kind == "attn":
        p = {"mix": layers.attn_params(cfg, kmix)}
    elif b.kind == "mla":
        p = {"mix": layers.mla_params(cfg, kmix)}
    elif b.kind == "mamba":
        p = {"mix": ssm.mamba_params(cfg, kmix)}
    elif b.kind == "mlstm":
        p = {"mix": ssm.mlstm_params(cfg, kmix)}
    elif b.kind == "slstm":
        p = {"mix": ssm.slstm_params(cfg, kmix)}
    else:
        raise ValueError(f"unknown block kind {b.kind!r}")
    if b.ffn == "dense":
        p["ffn"] = layers.ffn_params(cfg, kffn, d_ff=b.d_ff or cfg.d_ff)
    elif b.ffn == "moe":
        p["ffn"] = moe.moe_params(cfg, kffn)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.stages) + 2)
    dt = jnp.dtype(cfg.dtype)
    params: Dict[str, Any] = {
        "embed": {"tok": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), dt)
                  * 0.02},
        "final_norm": layers.norm_params(cfg, cfg.d_model),
    }
    if cfg.pos_emb == "learned":
        params["embed"]["pos"] = (
            jax.random.normal(jax.random.fold_in(keys[0], 1),
                              (cfg.max_seq, cfg.d_model), dt) * 0.02)
    if not cfg.tie_embeddings:
        params["head"] = {"w": jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab), dt) * cfg.d_model ** -0.5}
    stages = {}
    for si, st in enumerate(cfg.stages):
        skey = keys[2 + si]
        blocks = {}
        for bj, b in enumerate(st.pattern):
            bkeys = jax.random.split(jax.random.fold_in(skey, bj), st.repeat)
            blocks[f"b{bj}"] = jax.vmap(
                functools.partial(_block_params, cfg, b))(bkeys)
        stages[f"s{si}"] = blocks
    params["stages"] = stages
    return params


def zo_group_fn(path: str) -> Optional[str]:
    """Leaf path -> LeZO layer group (stacked axis 0) or None (always on)."""
    if path.startswith("stages/"):
        parts = path.split("/")
        return f"{parts[1]}.{parts[2]}"          # e.g. "s0.b3"
    return None


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def count_active_params(cfg: ModelConfig, params) -> int:
    """MoE-aware 'active per token' count for MODEL_FLOPS = 6*N_active*D."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        ps = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = leaf.size
        if "we_g" in ps or "we_u" in ps or "we_d" in ps:
            n = n * cfg.top_k // cfg.n_experts
        if "embed/tok" in ps or "embed/pos" in ps:
            n = 0  # embedding lookup is not a matmul
        total += n
    return total


# --------------------------------------------------------------- forward
_MIX_FWD = {"attn": layers.attn_fwd, "mla": layers.mla_fwd,
            "mamba": ssm.mamba_fwd, "mlstm": ssm.mlstm_fwd,
            "slstm": ssm.slstm_fwd}


def _run_block(cfg, b: BlockCfg, p, x, *, mode, cache, pos, pc=None,
               pages=None):
    if pc is not None and (b.kind != "attn" or b.ffn == "moe"):
        raise NotImplementedError(
            f"virtual perturbation covers attn + dense blocks; got "
            f"{b.kind}+{b.ffn} (use forward_backend='materialized')")
    if mode == "paged" and b.kind != "attn":
        raise NotImplementedError(
            f"paged serving covers attn mixers only; got {b.kind!r} "
            "(the engine falls back to the lockstep path — DESIGN.md §12)")
    mix_kw = {} if pc is None else {"pc": pc.child("mix")}
    if pages is not None:
        mix_kw["pages"] = pages
    mix_out, new_cache = _MIX_FWD[b.kind](cfg, p["mix"], x, mode=mode,
                                          cache=cache, pos=pos, **mix_kw)
    x = x + mix_out
    aux = jnp.zeros((), F32)
    if b.ffn == "dense":
        ffn_kw = {} if pc is None else {"pc": pc.child("ffn")}
        x = x + layers.ffn_fwd(cfg, p["ffn"], x, d_ff=b.d_ff or cfg.d_ff,
                               **ffn_kw)
    elif b.ffn == "moe":
        y, aux = moe.moe_fwd(cfg, p["ffn"], x)
        x = x + y
    return x, new_cache, aux


def forward(cfg: ModelConfig, params, tokens, *, mode="train", caches=None,
            pos=0, embeds=None, perturb=None, pages=None):
    """tokens: (B, S) int32, or ``embeds``: (B, S, D) for stub frontends.

    mode: train (no cache) | prefill (build cache) | decode (S==1, use+
    advance cache) | paged (serving engine bucket: ``caches`` is the
    paged KV arena, ``pages`` the (B, max_pages) page table and ``pos``
    a (B,) per-lane start position — DESIGN.md §12).  Returns
    (hidden (B,S,D), new_caches, aux_loss).

    ``perturb`` (fused.PerturbCtx) runs the forward against the virtually
    perturbed weights theta + s*eps*z: every weight read regenerates its
    z slice from the counter RNG (per-layer predicated by the LeZO
    masks), so the loss equals the materialized perturb-forward-restore
    sequence's without any parameter writes (DESIGN.md §10).
    """
    P = 0 if (perturb is None or perturb.pair is None) else perturb.pair.n
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    elif perturb is None:
        x = params["embed"]["tok"][tokens]
    elif P:
        # stacked probes ride the batch axis p-major: (P, B, S, D) ->
        # (P*B, S, D), so every probe-agnostic op (attention, rope,
        # residuals) runs unchanged and only weight reads split by probe
        x = fused_ref.pembed_stack(
            params["embed"]["tok"], tokens,
            fused_ref.layer_seed(perturb.seed, "embed/tok"), perturb.scale)
        x = x.reshape((-1,) + x.shape[2:])
    else:
        x = fused_ref.pembed(params["embed"]["tok"], tokens,
                             fused_ref.layer_seed(perturb.seed, "embed/tok"),
                             perturb.scale)
    if cfg.pos_emb == "learned":
        S = x.shape[1]
        if mode == "paged":
            ppos = jnp.asarray(pos)[:, None] + jnp.arange(S)[None, :]
            x = x + params["embed"]["pos"][ppos]
        elif perturb is None:
            x = x + lax.dynamic_slice_in_dim(params["embed"]["pos"], pos, S, 0)
        elif P:
            rows = fused_ref.ppos_stack(
                params["embed"]["pos"], pos, S,
                fused_ref.layer_seed(perturb.seed, "embed/pos"),
                perturb.scale)                                # (P, S, D)
            x = (x.reshape(P, -1, *x.shape[1:]) + rows[:, None]
                 ).reshape(x.shape)
        else:
            x = x + fused_ref.ppos(params["embed"]["pos"], pos, S,
                                   fused_ref.layer_seed(perturb.seed,
                                                        "embed/pos"),
                                   perturb.scale)

    aux_total = jnp.zeros((), F32)
    new_caches: Dict[str, Any] = {}
    for si, st in enumerate(cfg.stages):
        sp = params["stages"][f"s{si}"]
        scache = caches[f"s{si}"] if caches is not None else None
        if perturb is not None:
            # per-block LeZO masks + layer ids ride the scan alongside the
            # stacked params; group names match models.lm.zo_group_fn
            pmasks = {f"b{bj}": perturb.group_mask(f"s{si}.b{bj}", st.repeat)
                      for bj in range(len(st.pattern))}
            lids = jnp.arange(st.repeat, dtype=jnp.uint32)

        def body(x_aux, sliced):
            x, aux = x_aux
            if perturb is None:
                bp_all, bc_all = sliced
            else:
                bp_all, bc_all, pm, lid = sliced
            ncs = {}
            for bj, b in enumerate(st.pattern):
                bc = bc_all[f"b{bj}"] if bc_all is not None else None
                pc = (None if perturb is None else
                      perturb.block(f"stages/s{si}/b{bj}", lid,
                                    pm[f"b{bj}"]))
                x, nc, a = _run_block(cfg, b, bp_all[f"b{bj}"], x,
                                      mode=mode, cache=bc, pos=pos, pc=pc,
                                      pages=pages)
                aux = aux + a
                if nc is not None:
                    ncs[f"b{bj}"] = nc
            return (x, aux), (ncs if ncs else None)

        xs = ((sp, scache) if perturb is None
              else (sp, scache, pmasks, lids))
        if st.repeat == 1:
            squeeze = lambda t: (jax.tree.map(lambda a: a[0], t)
                                 if t is not None else None)
            (x, aux_total), nc = body((x, aux_total),
                                      tuple(squeeze(t) for t in xs))
            if nc is not None:
                new_caches[f"s{si}"] = jax.tree.map(lambda a: a[None], nc)
        else:
            (x, aux_total), nc = lax.scan(body, (x, aux_total), xs)
            if nc is not None:
                new_caches[f"s{si}"] = nc
    if perturb is None:
        x = layers.apply_norm(cfg, params["final_norm"], x)
    else:
        x = perturb.leaf("final_norm").apply_norm(cfg, params["final_norm"],
                                                  x)
    return x, (new_caches if new_caches else None), aux_total


def _head_matrix(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"]["tok"].T
    return params["head"]["w"]


def logits_fn(cfg, params, hidden):
    return (hidden @ _head_matrix(cfg, params)).astype(F32)


def head_widths(S: int) -> Tuple[int, ...]:
    """The trailing windows ``chunked_ce`` can run the head over: powers
    of two from 8 below S, then S itself (the whole sequence)."""
    ws, w = [], 8
    while w < S:
        ws.append(w)
        w *= 2
    return tuple(ws) + (S,)


def head_rung(loss_mask):
    """Index into ``head_widths(S)`` of the narrowest trailing window
    that holds every nonzero of ``loss_mask`` (..., S); an all-zero
    mask takes the narrowest.  Computed on the device."""
    S = loss_mask.shape[-1]
    cols = jnp.any(loss_mask != 0, axis=tuple(range(loss_mask.ndim - 1)))
    need = jnp.where(jnp.any(cols), S - jnp.argmax(cols), 0)
    return jnp.searchsorted(jnp.asarray(head_widths(S)), need).astype(
        jnp.int32)


def chunked_ce(cfg, params, hidden, labels, loss_mask, perturb=None,
               rung=None):
    """Mean CE over masked positions without materializing (B,S,V) logits.

    The head runs only over the trailing window ``head_widths(S)[rung]``
    (``rung`` defaults to ``head_rung(loss_mask)``; ``lax.switch``
    picks the window on the device): every masked position lies inside
    it, so the loss is the same mean over the same positions.  A caller
    that vmaps over batch shards passes the whole batch's ``rung`` in
    unbatched, so the switch stays a switch.

    Under a paired ctx ``hidden`` is (P*B, S, D) (p-major); each probe's
    CE runs the *literally unpaired* program on its slice (a Python loop
    over the static P), so the (P,) loss vector is bit-identical to P
    separate forwards by construction — XLA's float association inside
    the fused scan body is not stable across batch shapes, so a stacked
    head reduction cannot make that guarantee (the transformer blocks,
    where the W traffic lives, still share the paired pass)."""
    if rung is None:
        rung = head_rung(loss_mask)
    P = 0 if (perturb is None or perturb.pair is None) else perturb.pair.n
    if P:
        B0 = hidden.shape[0] // P
        return jnp.stack([
            chunked_ce(cfg, params, hidden[pi * B0:(pi + 1) * B0], labels,
                       loss_mask, perturb=perturb.probe(pi), rung=rung)
            for pi in range(P)])
    B, S, D = hidden.shape
    widths = head_widths(S)
    tr = obs.get_tracer()
    if tr.enabled and not obs.tracing():
        tr.count(obs.CTR_LOSS_HEAD_ROWS, B * widths[int(rung)])
    W = _head_matrix(cfg, params)
    if perturb is not None:
        # tied head reads embed/tok through a transpose: trans counters
        # with the stored row length keep z identical to the axpy's
        head = perturb.leaf("embed/tok" if cfg.tie_embeddings else "head/w")
        head_kw = ({"trans": True, "ld": cfg.d_model}
                   if cfg.tie_embeddings else {})

    def body(carry, inp):
        h, y, m = inp
        if perturb is None:
            lg = (h @ W).astype(F32)                          # (B,chunk,V)
        else:
            lg = head.matmul(h, W, **head_kw).astype(F32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(m)), None

    def window(w, h, y, m):
        chunk = min(CE_CHUNK, w)
        assert w % chunk == 0
        n = w // chunk
        resh = lambda a: (a[:, S - w:].reshape(B, n, chunk, *a.shape[2:])
                          .swapaxes(0, 1))
        (tot, cnt), _ = lax.scan(
            body, (jnp.zeros((), F32), jnp.zeros((), F32)),
            (resh(h), resh(y), resh(m.astype(F32))))
        return tot / jnp.maximum(cnt, 1.0)

    return lax.switch(rung, [functools.partial(window, w) for w in widths],
                      hidden, labels, loss_mask)


def lm_loss(cfg: ModelConfig, params, batch, aux_coef=0.0, perturb=None,
            rung=None):
    """batch: {tokens (B,S) int32, labels (B,S) int32, loss_mask (B,S)} or
    {embeds (B,S,D), labels, loss_mask} for stub-frontend archs.

    ``perturb`` (fused.PerturbCtx): evaluate loss(theta + s*eps*z)
    virtually — see forward().  A paired ctx (``perturb.pair``) runs all
    P stacked probes through ONE forward and returns a (P,) loss vector
    (probe order = ctx order; ``fused.make_pair_ctx`` puts +eps first).
    ``rung``: the loss head's window, as ``chunked_ce`` takes it."""
    hidden, _, aux = forward(cfg, params, batch.get("tokens"),
                             embeds=batch.get("embeds"), mode="train",
                             perturb=perturb)
    with obs.stage(obs.LOSS_HEAD):
        loss = chunked_ce(cfg, params, hidden, batch["labels"],
                          batch["loss_mask"], perturb=perturb, rung=rung)
    return loss + aux_coef * aux


def lm_loss_pair(cfg: ModelConfig, params, batch, aux_coef=0.0,
                 perturb=None):
    """The paired-probe entry point: ``perturb`` must be a stacked ctx
    (``fused.make_pair_ctx`` / ``make_stack_ctx``); returns the (P,)
    per-probe loss vector from one fused forward.  Exists as an explicit
    surface for callers that want the pair contract checked."""
    if perturb is None or perturb.pair is None:
        raise ValueError("lm_loss_pair requires a stacked PerturbCtx "
                         "(fused.make_pair_ctx / make_stack_ctx)")
    return lm_loss(cfg, params, batch, aux_coef=aux_coef, perturb=perturb)


# ---------------------------------------------------------------- serving
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Build an empty cache pytree matching forward(mode='decode')."""
    dt = jnp.dtype(dtype or cfg.dtype)
    B = batch
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    caches: Dict[str, Any] = {}
    for si, st in enumerate(cfg.stages):
        blocks = {}
        for bj, b in enumerate(st.pattern):
            R = st.repeat
            if b.kind == "attn":
                c = {"k": jnp.zeros((R, B, max_seq, KV, dh), dt),
                     "v": jnp.zeros((R, B, max_seq, KV, dh), dt)}
            elif b.kind == "mla":
                c = {"ckv": jnp.zeros((R, B, max_seq, cfg.kv_lora), dt),
                     "kr": jnp.zeros((R, B, max_seq, cfg.rope_head_dim), dt)}
            elif b.kind == "mamba":
                Di = cfg.mamba_d_inner
                c = {"conv": jnp.zeros((R, B, cfg.mamba_conv - 1, Di), dt),
                     "ssm": jnp.zeros((R, B, Di, cfg.mamba_d_state), F32)}
            elif b.kind == "mlstm":
                Di = cfg.lstm_d_inner
                dhh = Di // H
                c = {"conv": jnp.zeros((R, B, 3, Di), dt),
                     "C": jnp.zeros((R, B, H, dhh, dhh), F32),
                     "n": jnp.zeros((R, B, H, dhh), F32),
                     "m": jnp.full((R, B, H), -jnp.inf, F32)}
            else:  # slstm
                dhh = cfg.d_model // H
                z = jnp.zeros((R, B, H, dhh), F32)
                c = {"c": z, "n": z, "h": z,
                     "m": jnp.full((R, B, H, dhh), -jnp.inf, F32)}
            blocks[f"b{bj}"] = c
        caches[f"s{si}"] = blocks
    return caches


def serve_step(cfg: ModelConfig, params, caches, token, pos, embeds=None):
    """One decode step. token: (B, 1) int32; pos: scalar int32 (current
    length of the cache). Returns (logits (B, V) f32, new_caches)."""
    hidden, new_caches, _ = forward(cfg, params, token, mode="decode",
                                    caches=caches, pos=pos, embeds=embeds)
    return logits_fn(cfg, params, hidden[:, -1]), new_caches


def prefill(cfg: ModelConfig, params, tokens, max_seq: int, embeds=None):
    """Run the prompt through the model, returning (logits_last, caches)."""
    B = (tokens if tokens is not None else embeds).shape[0]
    caches = init_cache(cfg, B, max_seq)
    hidden, new_caches, _ = forward(cfg, params, tokens, mode="prefill",
                                    caches=caches, pos=0, embeds=embeds)
    return logits_fn(cfg, params, hidden[:, -1]), new_caches


# --------------------------------------------------------- paged serving
def supports_paged(cfg: ModelConfig) -> bool:
    """True when every mixer is attn — the block family the paged
    serving engine covers (DESIGN.md §12); SSM/MLA state is per-lane
    fixed-size and served by the lockstep path instead."""
    from repro.models import frontends
    return (not frontends.uses_embeds(cfg)
            and all(b.kind == "attn" for s in cfg.stages for b in s.pattern))


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=None):
    """The serving engine's KV arena: one (R, n_pages, page_size, KV, dh)
    buffer per stage-block leaf, shared by every request via per-lane
    page tables (DESIGN.md §12).  Page 0 is reserved as the trash page —
    inactive lanes write there; the allocator never hands it out."""
    dt = jnp.dtype(dtype or cfg.dtype)
    KV, dh = cfg.n_kv_heads, cfg.head_dim
    caches: Dict[str, Any] = {}
    for si, st in enumerate(cfg.stages):
        blocks = {}
        for bj, b in enumerate(st.pattern):
            if b.kind != "attn":
                raise NotImplementedError(
                    f"paged serving covers attn mixers only; "
                    f"{cfg.name} stage {si} has {b.kind!r} "
                    "(use the lockstep serve path)")
            shape = (st.repeat, n_pages, page_size, KV, dh)
            blocks[f"b{bj}"] = {"k": jnp.zeros(shape, dt),
                                "v": jnp.zeros(shape, dt)}
        caches[f"s{si}"] = blocks
    return caches


def paged_step(cfg: ModelConfig, params, arena, tokens, pages, pos, sel):
    """One bucketed serving call — a prefill chunk or a batched decode
    step are the same computation at different (B, C) buckets
    (DESIGN.md §12).

    tokens: (B, C) int32 — C == 1 for a decode step, C == prefill_chunk
    for a prefill call; pages: (B, max_pages) int32 page-table rows
    (entry 0 = trash page); pos: (B,) int32 absolute position of
    ``tokens[:, 0]``; sel: (B,) int32 chunk index whose logits each lane
    returns (the last valid prompt token for a final prefill chunk, 0
    for decode).  Returns (logits (B, V) f32, new_arena).
    """
    hidden, new_arena, _ = forward(cfg, params, tokens, mode="paged",
                                   caches=arena, pos=pos, pages=pages)
    h_sel = jnp.take_along_axis(hidden, sel[:, None, None], axis=1)[:, 0]
    return logits_fn(cfg, params, h_sel), new_arena
