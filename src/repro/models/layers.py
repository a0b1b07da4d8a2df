"""Core transformer layers: norms, RoPE, flash attention (GQA + MLA), FFN.

All functions are pure; a *block*'s parameters arrive unstacked (one
layer's slice — the stage scan in lm.py slices the stacked leaves).
Activations are (B, S, D) in cfg.dtype; matmuls accumulate in f32 via
``preferred_element_type``.

Attention is a chunked flash implementation (double lax.scan over q- and
k-chunks with running log-sum-exp), so peak memory is O(q_chunk * k_chunk)
instead of O(S^2) — required for the 32k-prefill shapes to fit a v5e.
Fully-masked k-chunks are skipped with a real ``lax.cond`` branch, halving
causal-attention FLOPs at the HLO level.

Model stack (DESIGN.md §8); paged attention: DESIGN.md §12.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed import ctx

F32 = jnp.float32
NEG_INF = -1e30


# ----------------------------------------------------------------- norms
def rms_norm(x, scale, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    y = x.astype(F32) * lax.rsqrt(var + eps) * scale.astype(F32)
    return y.astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps) * scale.astype(F32) + bias.astype(F32)
    return y.astype(x.dtype)


def apply_norm(cfg, p, x):
    if cfg.norm == "rms":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


def norm_params(cfg, d):
    if cfg.norm == "rms":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


# ------------------------------------------------------------------ rope
def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(d: int, theta: float, scaling=None):
    """(d/2,) inverse frequencies; with a ``config.YarnCfg`` the YaRN
    blend: dims below the beta_fast bound keep theta's frequency, dims
    above the beta_slow bound take it divided by ``factor``, a linear
    ramp between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if scaling is None:
        return freqs
    s = scaling

    def bound(rotations):
        return (d * math.log(s.original_max_position_embeddings
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(bound(s.beta_fast)), 0)
    hi = min(math.ceil(bound(s.beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return freqs / s.factor * ramp + freqs * (1.0 - ramp)


def rope(x, positions, theta=10000.0, scaling=None):
    """x: (B, S, n, d) with d even; positions: (S,) shared across the
    batch, or (B, S) per-lane (the paged serving path, where every lane
    sits at its own decode position — DESIGN.md §12).  ``scaling``: a
    ``config.YarnCfg`` or None (plain RoPE)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)
    ang = positions.astype(F32)[..., None] * freqs       # (S, d/2) | (B, S, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------- flash attention
@functools.partial(jax.jit, static_argnames=("causal", "q_chunk", "k_chunk",
                                              "k_offset", "sm_scale"))
def flash_attention(q, k, v, *, causal=True, q_offset=0, k_offset=0,
                    q_chunk=512, k_chunk=512, sm_scale=None):
    """q: (B,Sq,KV,G,dh), k/v: (B,Sk,KV,dh). Returns (B,Sq,KV,G,dh).

    ``q_offset``: absolute position of q[0] (for prefill continuation).
    ``k_offset``: position of k[0]; a negative value marks leading
    always-visible tokens (prefix tuning).  ``sm_scale``: the softmax
    scale, dh ** -0.5 when None.
    """
    B, Sq, KV, G, dh = q.shape
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    assert Sq % q_chunk == 0
    if Sk % k_chunk:  # pad keys (padded slots masked out via position test)
        pad = k_chunk - Sk % k_chunk
        k = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
    nq, nk = Sq // q_chunk, k.shape[1] // k_chunk
    scale = dh ** -0.5 if sm_scale is None else sm_scale
    q_offset = jnp.asarray(q_offset, jnp.int32)

    qc = q.reshape(B, nq, q_chunk, KV, G, dh).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, k_chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, KV, dh).transpose(1, 0, 2, 3, 4)

    def q_body(_, qi_and_chunk):
        qi, qblk = qi_and_chunk
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def k_body(carry, ki_and_chunk):
            ki, kblk, vblk = ki_and_chunk
            k_idx = ki * k_chunk + jnp.arange(k_chunk)
            k_pos = k_offset + k_idx

            def compute(carry):
                m, l, acc = carry
                s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                               preferred_element_type=F32) * scale
                msk = k_idx[None, :] < Sk          # mask key padding
                if causal:
                    msk = msk & (q_pos[:, None] >= k_pos[None, :])
                s = jnp.where(msk[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(qblk.dtype), vblk,
                                preferred_element_type=F32)
                acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
                return m_new, l_new, acc_new

            if causal:
                needed = k_offset + ki * k_chunk <= q_pos[-1]
                carry = lax.cond(needed, compute, lambda c: c, carry)
            else:
                carry = compute(carry)
            return carry, None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, F32)
        l0 = jnp.zeros((B, KV, G, q_chunk), F32)
        a0 = jnp.zeros((B, q_chunk, KV, G, dh), F32)
        (m, l, acc), _ = lax.scan(
            k_body, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return None, out.astype(q.dtype)

    _, out = lax.scan(q_body, None, (jnp.arange(nq), qc))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, dh)


def decode_attention(q, k_cache, v_cache, cur_len):
    """Single-token attention over a (possibly partially filled) cache.

    q: (B,1,KV,G,dh); caches: (B,Smax,KV,dh); cur_len: int32 — number of
    valid cache entries *including* the current token.  Scalar ``cur_len``
    is the lockstep path (every lane at the same depth); a (B,) array is
    the continuous-batching path (per-lane depths).
    """
    B, _, KV, G, dh = q.shape
    Smax = k_cache.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_cache,
                   preferred_element_type=F32) * (dh ** -0.5)
    cur = jnp.reshape(jnp.asarray(cur_len), (-1, 1))     # (1|B, 1)
    valid = jnp.arange(Smax)[None, :] < cur              # (1|B, Smax)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), v_cache,
                     preferred_element_type=F32)
    return out.astype(q.dtype)


def paged_attention(q, k, v, q_positions):
    """Causal attention over page-gathered caches (DESIGN.md §12).

    q: (B,C,KV,G,dh) — C query tokens per lane (C==1 for a decode step,
    C==prefill_chunk for a prefill call); k/v: (B,Smax,KV,dh), the lane's
    page table gathered back into position order, so buffer index s IS
    absolute position s; q_positions: (B,C) absolute position per query.

    The single causal test ``s <= q_position`` doubles as the validity
    mask: pages are written front-to-back, so every position <= the
    query's is live and everything beyond it is trash-page garbage.
    Dense (not flash) on purpose — serving buckets keep Smax at
    max_seq-bucket scale, and one (C, Smax) score block per lane is the
    flash-decode memory shape anyway.
    """
    B, C, KV, G, dh = q.shape
    Smax = k.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=F32) * (dh ** -0.5)
    msk = jnp.arange(Smax)[None, None, :] <= q_positions[:, :, None]
    s = jnp.where(msk[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype)


# --------------------------------------------------------------- GQA block
def attn_params(cfg, key):
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    std = D ** -0.5
    dt = jnp.dtype(cfg.dtype)
    p = {
        "norm": norm_params(cfg, D),
        "wq": jax.random.normal(ks[0], (D, H * dh), dt) * std,
        "wk": jax.random.normal(ks[1], (D, KV * dh), dt) * std,
        "wv": jax.random.normal(ks[2], (D, KV * dh), dt) * std,
        "wo": jax.random.normal(ks[3], (H * dh, D), dt) * (H * dh) ** -0.5,
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
    return p


def attn_fwd(cfg, p, x, *, mode, cache=None, pos=0, pc=None, pages=None):
    """mode: train | prefill | decode | paged.  Returns (out, new_cache).

    ``pc`` (fused.LayerPerturb) switches every weight read to its
    virtually perturbed view — loss(theta + s*eps*z) with no perturbed
    weights ever materialized (DESIGN.md §10); None is the plain path.

    mode="paged" is the serving engine's bucketed call (DESIGN.md §12):
    ``cache`` holds this layer's arena slice {"k"/"v": (P, psz, KV, dh)},
    ``pages`` is the (B, max_pages) page table (page 0 = trash), and
    ``pos`` is a (B,) per-lane start position.  The new K/V land at
    page ``pages[b, pos_b // psz]`` slot ``pos_b % psz``; attention then
    gathers each lane's pages back into position order.
    """
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    if pc is not None and "pk" in p:
        raise NotImplementedError(
            "virtual perturbation does not cover prefix-KV leaves")
    mm = (lambda a, w, name: a @ w) if pc is None else pc.matmul
    h = (apply_norm(cfg, p["norm"], x) if pc is None
         else pc.apply_norm(cfg, p["norm"], x, "norm"))
    q = mm(h, p["wq"], "wq").reshape(B, S, H, dh)
    k = mm(h, p["wk"], "wk").reshape(B, S, KV, dh)
    v = mm(h, p["wv"], "wv").reshape(B, S, KV, dh)
    if cfg.qk_norm:
        if pc is None:
            q = rms_norm(q, p["q_norm"]["scale"])
            k = rms_norm(k, p["k_norm"]["scale"])
        else:
            q = pc.rms_norm(q, p["q_norm"]["scale"], "q_norm/scale")
            k = pc.rms_norm(k, p["k_norm"]["scale"], "k_norm/scale")
    if mode == "paged":
        positions = jnp.asarray(pos)[:, None] + jnp.arange(S)[None, :]
    else:
        positions = pos + jnp.arange(S)
    if cfg.pos_emb == "rope":
        q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    q = q.reshape(B, S, KV, G, dh)
    if mode not in ("decode", "paged"):
        mesh = ctx.get_mesh()
        nm = mesh.shape.get("model", 1) if mesh is not None else 1
        if mesh is not None and KV % nm == 0:
            # Pin q/k/v head-sharded once per layer so the flash scan sees
            # a stable layout (otherwise the partitioner re-gathers k/v in
            # f32 every inner iteration).
            k = ctx.constrain(k, "batch", None, "model", None)
            v = ctx.constrain(v, "batch", None, "model", None)
            q = ctx.constrain(q, "batch", None, "model", None, None)
        elif mesh is not None and nm > 1 and S % nm == 0:
            # Heads don't divide the model axis (e.g. 56 heads / 16): left
            # alone, the partitioner keeps dh sharded and ALL-REDUCES the
            # score blocks of every flash iteration (TBs/step).  Instead
            # shard attention over *query stripes* (sequence parallel):
            # one bf16 k/v gather per layer, zero score collectives.
            q = ctx.constrain(q, "batch", "model", None, None, None)
            k = ctx.constrain(k, "batch", None, None, None)
            v = ctx.constrain(v, "batch", None, None, None)

    if mode == "paged":
        Pn, psz = cache["k"].shape[0], cache["k"].shape[1]
        page = pages[jnp.arange(B)[:, None], positions // psz]  # (B, S)
        flat = (page * psz + positions % psz).reshape(-1)
        k_arena = cache["k"].reshape(Pn * psz, KV, dh).at[flat].set(
            k.reshape(B * S, KV, dh)).reshape(Pn, psz, KV, dh)
        v_arena = cache["v"].reshape(Pn * psz, KV, dh).at[flat].set(
            v.reshape(B * S, KV, dh)).reshape(Pn, psz, KV, dh)
        kg = k_arena[pages].reshape(B, -1, KV, dh)   # (B, max_pg*psz, ...)
        vg = v_arena[pages].reshape(B, -1, KV, dh)
        o = paged_attention(q, kg, vg, positions)
        new_cache = {"k": k_arena, "v": v_arena}
    elif mode == "decode":
        k_cache = lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        o = decode_attention(q, k_cache, v_cache, pos + 1)
        new_cache = {"k": k_cache, "v": v_cache}
    elif "pk" in p:  # prefix tuning: always-visible learned KV pairs
        P = p["pk"].shape[0]
        pk = jnp.broadcast_to(p["pk"].astype(k.dtype), (B, P, KV, dh))
        pv = jnp.broadcast_to(p["pv"].astype(v.dtype), (B, P, KV, dh))
        kf = jnp.concatenate([pk, k], axis=1)
        vf = jnp.concatenate([pv, v], axis=1)
        o = flash_attention(q, kf, vf, causal=True, q_offset=pos, k_offset=-P,
                            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk)
        new_cache = None
    else:
        o = flash_attention(q, k, v, causal=True, q_offset=pos,
                            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk)
        new_cache = None
        if mode == "prefill":
            Smax = cache["k"].shape[1]
            pad = [(0, 0), (0, Smax - S), (0, 0), (0, 0)]
            new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    out = mm(o.reshape(B, S, H * dh), p["wo"], "wo")
    return out.astype(x.dtype), new_cache


# --------------------------------------------------------------- MLA block
def mla_params(cfg, key):
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, lora = cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora
    ks = jax.random.split(key, 5)
    std = D ** -0.5
    dt = jnp.dtype(cfg.dtype)
    return {
        "norm": norm_params(cfg, D),
        "wq": jax.random.normal(ks[0], (D, H * (dn + dr)), dt) * std,
        "wdkv": jax.random.normal(ks[1], (D, lora + dr), dt) * std,
        "kv_norm": {"scale": jnp.ones((lora,), jnp.float32)},
        "wuk": jax.random.normal(ks[2], (lora, H * dn), dt) * lora ** -0.5,
        "wuv": jax.random.normal(ks[3], (lora, H * dn), dt) * lora ** -0.5,
        "wo": jax.random.normal(ks[4], (H * dn, D), dt) * (H * dn) ** -0.5,
    }


def mla_fwd(cfg, p, x, *, mode, cache=None, pos=0):
    """DeepSeek-V2 multi-head latent attention.

    Cache holds only (c_kv, k_rope): (lora + rope_dim) per token.  Decode
    uses the weight-absorbed latent form — scores and values are computed
    directly against the latent cache, never materializing per-head K/V.
    The softmax scale is (dn + dr) ** -0.5, times mscale(mscale_all_dim)
    squared under a YaRN ``rope_scaling``.
    """
    B, S, D = x.shape
    H, dn, dr, lora = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora
    h = apply_norm(cfg, p["norm"], x)
    q = (h @ p["wq"]).reshape(B, S, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    dkv = h @ p["wdkv"]
    ckv = rms_norm(dkv[..., :lora], p["kv_norm"]["scale"],
                   cfg.norm_eps)                              # (B,S,lora)
    kr = dkv[..., lora:].reshape(B, S, 1, dr)
    positions = pos + jnp.arange(S)
    qr = rope(qr, positions, cfg.rope_theta, cfg.rope_scaling)
    kr = rope(kr, positions, cfg.rope_theta, cfg.rope_scaling)
    scale_fix = (dn + dr) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale_fix *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2

    if mode == "decode":
        ckv_c = lax.dynamic_update_slice(cache["ckv"], ckv, (0, pos, 0))
        kr_c = lax.dynamic_update_slice(cache["kr"], kr[:, :, 0], (0, pos, 0))
        # absorbed: q_lat[b,h,l] = sum_d qn[b,h,d] * wuk[l, h*dn+d]
        wuk = p["wuk"].reshape(lora, H, dn)
        q_lat = jnp.einsum("bqhd,lhd->bqhl", qn, wuk, preferred_element_type=F32)
        s = jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv_c.astype(F32),
                       preferred_element_type=F32)
        s += jnp.einsum("bqhd,bsd->bhqs", qr.astype(F32), kr_c.astype(F32),
                        preferred_element_type=F32)
        s *= scale_fix
        valid = jnp.arange(ckv_c.shape[1]) < pos + 1
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqs,bsl->bqhl", pr, ckv_c.astype(F32),
                           preferred_element_type=F32)
        wuv = p["wuv"].reshape(lora, H, dn)
        o = jnp.einsum("bqhl,lhd->bqhd", o_lat, wuv, preferred_element_type=F32)
        o = o.astype(x.dtype)
        new_cache = {"ckv": ckv_c, "kr": kr_c}
    else:
        kn = jnp.einsum("bsl,lhd->bshd", ckv, p["wuk"].reshape(lora, H, dn))
        vv = jnp.einsum("bsl,lhd->bshd", ckv, p["wuv"].reshape(lora, H, dn))
        kfull = jnp.concatenate([kn, jnp.broadcast_to(kr, (B, S, H, dr))], -1)
        qfull = jnp.concatenate([qn, qr], -1).reshape(B, S, H, 1, dn + dr)
        vpad = jnp.pad(vv, [(0, 0), (0, 0), (0, 0), (0, dr)])
        o = flash_attention(qfull, kfull, vpad, causal=True, q_offset=pos,
                            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk,
                            sm_scale=scale_fix)
        o = o.reshape(B, S, H, dn + dr)[..., :dn]
        new_cache = None
        if mode == "prefill":
            Smax = cache["ckv"].shape[1]
            new_cache = {
                "ckv": jnp.pad(ckv, [(0, 0), (0, Smax - S), (0, 0)]),
                "kr": jnp.pad(kr[:, :, 0], [(0, 0), (0, Smax - S), (0, 0)]),
            }
    out = o.reshape(B, S, H * dn) @ p["wo"]
    return out.astype(x.dtype), new_cache


# --------------------------------------------------------------- dense FFN
def ffn_params(cfg, key, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    p = {"norm": norm_params(cfg, D)}
    if cfg.act == "silu":  # swiglu
        p["wg"] = jax.random.normal(ks[0], (D, F), dt) * D ** -0.5
        p["wu"] = jax.random.normal(ks[1], (D, F), dt) * D ** -0.5
    else:
        p["wi"] = jax.random.normal(ks[0], (D, F), dt) * D ** -0.5
    p["wd"] = jax.random.normal(ks[2], (F, D), dt) * F ** -0.5
    return p


def ffn_fwd(cfg, p, x, d_ff=None, pc=None):
    mm = (lambda a, w, name: a @ w) if pc is None else pc.matmul
    h = (apply_norm(cfg, p["norm"], x) if pc is None
         else pc.apply_norm(cfg, p["norm"], x, "norm"))
    if cfg.act == "silu":
        a = (jax.nn.silu(mm(h, p["wg"], "wg").astype(F32)).astype(x.dtype)
             * mm(h, p["wu"], "wu"))
    elif cfg.act == "gelu":
        a = jax.nn.gelu(mm(h, p["wi"], "wi").astype(F32)).astype(x.dtype)
    else:
        a = jax.nn.relu(mm(h, p["wi"], "wi"))
    return mm(a, p["wd"], "wd").astype(x.dtype)
