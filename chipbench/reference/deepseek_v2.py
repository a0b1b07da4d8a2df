"""Plain reference of DeepSeek-V2-Lite as the program runs it, and of one
ZO step with LeZO selection over its two layer groups.

The model (arXiv:2405.04434; the published config.json): a token
embedding; ``first_k_dense_replace`` dense layers, then MoE layers, each
pre-RMSNorm (eps ``rms_norm_eps``) with a residual around multi-head
latent attention and around the FFN; a final RMSNorm and an untied head.

  * MLA, no q-LoRA: q = h Wq split per head into a 128-wide "nope" part
    and a 64-wide rope part; c = RMSNorm(h Wdkv[:, :512]) and a rope key
    h Wdkv[:, 512:] shared by every head; k = [c Wuk | rope key],
    v = c Wuv; causal softmax at scale 192^-0.5 times m(mscale_all_dim)^2.
  * YaRN rope (``rope_scaling``), at every position: inverse frequencies
    base^(-2i/d) blended with the same over ``factor`` by a linear ramp
    between the dims that beta_fast and beta_slow rotations bound at
    ``original_max_position_embeddings``; cos and sin times
    m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln(factor) + 1.  Rope
    dims pair as halves (``assumed.rope_pairing``).
  * Dense FFN: SwiGLU of width ``intermediate_size``.
  * MoE FFN: softmax router over ``n_routed_experts``, greedy top-k
    (``num_experts_per_tok``), weights renormalized only where
    ``norm_topk_prob``; each expert's SwiGLU (width
    ``moe_intermediate_size``) runs on exactly the tokens routed to it,
    gathered by index, with nothing dropped; plus ``n_shared_experts``
    experts as one SwiGLU of n times the width, on every token.

Everything is computed in float32 with matmuls at ``HIGHEST`` precision,
one layer at a time (a MoE layer's weights are 2.3 GB in float32);
``mm="fp8"`` rounds every matmul's operands to float8 first (the router's
too), the precision below the configuration's bfloat16.  The weights and
the counter RNG are those of ``reference/opt.py``, whose model-free
helpers this module uses; every write of a perturbation or an update is
rounded to the leaf's type as it is written, by ``lax.reduce_precision``
(which no compiler folds away), as the program's sweeps store them; the
selection rule is this module's own copy.  Nothing here imports the
program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from reference import opt
from reference import rng

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
DENSE, MOE = "stages/s0/b0/", "stages/s1/b0/"
MIX = ("mix/norm/scale", "mix/wq", "mix/wdkv", "mix/kv_norm/scale",
       "mix/wuk", "mix/wuv", "mix/wo")
DENSE_FFN = ("ffn/norm/scale", "ffn/wg", "ffn/wu", "ffn/wd")
MOE_FFN = ("ffn/norm/scale", "ffn/router", "ffn/we_g", "ffn/we_u",
           "ffn/we_d", "ffn/ws_g", "ffn/ws_u", "ffn/ws_d")
EXPERT_BUCKET = 128      # the least length an expert's token list pads to
ROUNDING_SHARE = 1e-3    # the most a judged share may owe to rounding

matmul, to_tree, from_tree = opt.matmul, opt.to_tree, opt.from_tree


# ---------------------------------------------------------------- layout
def groups(cfg: Dict) -> Tuple[Tuple[str, str, int, Tuple[str, ...]], ...]:
    """(group name, path prefix, layers, leaf names) of the two stacked
    groups: the leading dense layers and the MoE layers after them."""
    n_dense = cfg["first_k_dense_replace"]
    return (("s0.b0", DENSE, n_dense, MIX + DENSE_FFN),
            ("s1.b0", MOE, cfg["num_hidden_layers"] - n_dense, MIX + MOE_FFN))


def layout(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str, str, float]]:
    """(path, shape, dtype, kind, std) of every leaf, in path order;
    kinds as in ``reference/opt.py``."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R, V, E = cfg["kv_lora_rank"], cfg["vocab_size"], cfg["n_routed_experts"]
    Fd, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = cfg["n_shared_experts"] * Fe
    dt = "bfloat16" if cfg["assumed"]["dtype"] == "bfloat16" else "float32"
    mix = {"mix/norm/scale": ((D,), "float32", "scale", 0.1),
           "mix/wq": ((D, H * (dn + dr)), dt, "normal", D ** -0.5),
           "mix/wdkv": ((D, R + dr), dt, "normal", D ** -0.5),
           "mix/kv_norm/scale": ((R,), "float32", "scale", 0.1),
           "mix/wuk": ((R, H * dn), dt, "normal", R ** -0.5),
           "mix/wuv": ((R, H * dv), dt, "normal", R ** -0.5),
           "mix/wo": ((H * dv, D), dt, "normal", (H * dv) ** -0.5)}
    ffn = {"ffn/norm/scale": ((D,), "float32", "scale", 0.1),
           "ffn/wg": ((D, Fd), dt, "normal", D ** -0.5),
           "ffn/wu": ((D, Fd), dt, "normal", D ** -0.5),
           "ffn/wd": ((Fd, D), dt, "normal", Fd ** -0.5),
           "ffn/router": ((D, E), "float32", "normal", D ** -0.5),
           "ffn/we_g": ((E, D, Fe), dt, "normal", D ** -0.5),
           "ffn/we_u": ((E, D, Fe), dt, "normal", D ** -0.5),
           "ffn/we_d": ((E, Fe, D), dt, "normal", Fe ** -0.5),
           "ffn/ws_g": ((D, Fs), dt, "normal", D ** -0.5),
           "ffn/ws_u": ((D, Fs), dt, "normal", D ** -0.5),
           "ffn/ws_d": ((Fs, D), dt, "normal", Fs ** -0.5)}
    leaves = [("embed/tok", (V, D), dt, "normal", 0.02),
              ("final_norm/scale", (D,), "float32", "scale", 0.1),
              ("head/w", (D, V), dt, "normal", D ** -0.5)]
    for _, prefix, L, names in groups(cfg):
        for n in names:
            shape, ldt, kind, std = {**mix, **ffn}[n]
            leaves.append((prefix + n, (L,) + shape, ldt, kind, std))
    return sorted(leaves)


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every leaf for run seed ``seed``, in one jitted call; path -> array."""
    return opt._all_leaves(tuple(layout(cfg)),
                           jnp.uint32(rng.weight_seed(seed)))


def weight_leaf(cfg: Dict, seed: int, path: str) -> jax.Array:
    (entry,) = [e for e in layout(cfg) if e[0] == path]
    return opt._leaf_jit(jnp.uint32(rng.weight_seed(seed)), *entry)


@jax.jit
def _row_along_z(leaf, leaf0, layer, step_seed, path_uid):
    """<row change, z>, <z, z>, max |z| and the largest |weight| at the
    start, over one layer's row of a leaf, z the step's direction there."""
    row = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    row0 = lax.dynamic_index_in_dim(leaf0, layer, 0, keepdims=False)
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), path_uid)
    z = rng.layer_normals(lseed, layer, row.shape)
    d = row.astype(F32) - row0.astype(F32)
    return (jnp.sum(d * z), jnp.sum(z * z), jnp.max(jnp.abs(z)),
            jnp.max(jnp.abs(row0.astype(F32))))


def applied_steps(cfg: Dict, seed: int, flat: Dict[str, jax.Array],
                  steps) -> Dict[str, float]:
    """How much of each step's intended update every leaf holds: for
    step t = (step seed, kept layers, intended scale -lr g), the change
    of ``flat`` from the run's starting weights projected on z_t over
    the rows step t updated, over that scale, under the key
    ``path@t``: 1 where the update landed, 0 where the state was left
    unchanged.

    Every stacked leaf is judged on every step that updated its rows:
    the program's row sweeps store each of a step's three writes, as
    ``perturb`` does here, so a sound run holds the reference's bits
    whatever the scale, and an update left out reads apart from it
    however far under an ulp it lies.  An unstacked leaf (embedding,
    final norm, head), whose sweeps a compiler may round once (keeping
    the perturbed value in excess precision, XLA's default) or three
    times, is judged only where the scale is at least an ulp of the
    largest weight it can hold by then (the start's largest plus every
    update's largest move), and at least ``ROUNDING_SHARE``^-1 / 2 ulps
    over the root of its size: below the first, what the stored weights
    keep of the update depends on how often the sweeps round (three
    roundings dither it in, one leaves most of it out); at or above it
    any order of roundings keeps the update whole and rounding is
    uncorrelated with z, so the projection reads the update itself,
    give or take the second bound's share.  Both sides of a comparison
    take the same keys: they depend on the start, the z and the scales
    alone."""
    group = {prefix: g for g, prefix, _, _ in groups(cfg)}
    out = {}
    for path, _, dtype, *_ in layout(cfg):
        theta0 = weight_leaf(cfg, seed, path)
        leaf, leaf0 = flat[path], theta0
        if not opt.stacked(path):
            leaf, leaf0 = leaf[None], leaf0[None]      # one pseudo-layer
        uid = jnp.uint32(rng.uid(path))
        ulp, row_size = float(jnp.finfo(dtype).eps), math.prod(leaf.shape[1:])
        reach = {}                      # row -> bound on its largest |weight|
        for t, (step_seed, active, scale) in enumerate(steps):
            rows = (active[group[path[:len(DENSE)]]] if opt.stacked(path)
                    else (0,))
            if not rows or scale == 0:
                continue
            num = den = 0.0
            for r in rows:
                a, b, zmax, m0 = _row_along_z(leaf, leaf0, jnp.int32(r),
                                              jnp.uint32(step_seed), uid)
                reach[r] = reach.get(r, float(m0)) + abs(scale) * float(zmax)
                num, den = num + float(a), den + float(b)
            floor = 0.0 if opt.stacked(path) else ulp * reach[0] * max(
                1.0, 0.5 / ROUNDING_SHARE / math.sqrt(row_size))
            if abs(scale) >= floor:
                out[f"{path}@{t}"] = num / den / scale
        del theta0
    return out


# ------------------------------------------------------------------ rope
def yarn_m(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(cfg: Dict, S: int) -> Tuple[np.ndarray, np.ndarray]:
    """cos, sin (S, dr/2) of positions 0 .. S-1, YaRN as published."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    f, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / f

    def dim(rot):            # the dim whose wavelength fits ``rot`` turns
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / ((hi - lo) or 0.001), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    m = yarn_m(f, rs["mscale"]) / yarn_m(f, rs["mscale_all_dim"])
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    return ((np.cos(ang) * m).astype(np.float32),
            (np.sin(ang) * m).astype(np.float32))


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_m(float(rs["factor"]), rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, cos, sin):
    """x (B, S, n, d): halves paired, x1 cos - x2 sin | x1 sin + x2 cos."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None], sin[None, :, None]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(F32)


def swiglu(h, wg, wu, wd, mm):
    return matmul(jax.nn.silu(matmul(h, wg, mm)) * matmul(h, wu, mm), wd, mm)


# ----------------------------------------------------------------- writes
def perturb(x, z, scales):
    """The step's writes in order: x <- type(x + s z) for each s of the
    (traced) f32 vector ``scales``, each rounded to x's type as it is
    stored."""
    fi = jnp.finfo(x.dtype)
    y = x.astype(F32)
    for i in range(scales.shape[0]):
        y = lax.reduce_precision(y + scales[i] * z, exponent_bits=fi.nexp,
                                 mantissa_bits=fi.nmant)
    return y.astype(x.dtype)


@jax.jit
def _shared(leaf, step_seed, path_uid, scales):
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), jnp.uint32(path_uid))
    return perturb(leaf, rng.layer_normals(lseed, 0, leaf.shape), scales)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_layer(leaf, layer, step_seed, path_uid, scales):
    row = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), jnp.uint32(path_uid))
    row = perturb(row, rng.layer_normals(lseed, layer, row.shape), scales)
    return lax.dynamic_update_index_in_dim(leaf, row, layer, 0)


# -------------------------------------------------------------- one layer
@functools.partial(jax.jit, static_argnames=("paths",))
def _rows(leaves, layer, step_seed, scales, paths):
    """One layer's row of each stacked leaf, read perturbed by
    z(step_seed) as the step writes it when ``scales`` is not empty."""
    out = []
    for path, leaf in zip(paths, leaves):
        row = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
        if scales.shape[0]:
            row = perturb(row, opt._z(step_seed, path, layer, row.shape),
                          scales)
        out.append(row)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("heads", "dims", "eps",
                                             "scale", "mm"))
def _mla(x, w, cos, sin, heads, dims, eps, scale, mm):
    """x + MLA(x); ``w`` the MIX rows, in order."""
    nrm, wq, wdkv, kvn, wuk, wuv, wo = w
    dn, dr, dv, R = dims
    B, S, _ = x.shape
    H = heads
    h = rms(x, nrm, eps)
    q = matmul(h, wq, mm).reshape(B, S, H, dn + dr)
    dkv = matmul(h, wdkv, mm)
    c = rms(dkv[..., :R], kvn, eps)
    kr = _rotate(dkv[..., R:].reshape(B, S, 1, dr), cos, sin)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], -1)
    k = jnp.concatenate([matmul(c, wuk, mm).reshape(B, S, H, dn),
                         jnp.broadcast_to(kr, (B, S, H, dr))], -1)
    v = matmul(c, wuv, mm).reshape(B, S, H, dv)

    def one(a):                         # one sequence, causal
        qs, ks, vs = a
        s = opt._einsum("qhd,khd->hqk", qs, ks, mm) * scale
        keep = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return opt._einsum("hqk,hdk->qhd", p, jnp.transpose(vs, (1, 2, 0)),
                           mm)

    o = lax.map(one, (q, k, v))
    return x + matmul(o.reshape(B, S, H * dv), wo, mm)


@functools.partial(jax.jit, static_argnames=("eps", "mm"))
def _dense_ffn(x, w, eps, mm):
    nrm, wg, wu, wd = w
    return x + swiglu(rms(x, nrm, eps), wg, wu, wd, mm)


@functools.partial(jax.jit, static_argnames=("k", "renorm", "eps", "mm"))
def _route(x, nrm, router, k, renorm, eps, mm):
    """The FFN's normed input (T, D) and each token's top-k (weight,
    expert)."""
    h = rms(x, nrm, eps).reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(matmul(h, router, mm), axis=-1)
    w, e = lax.top_k(probs, k)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return h, w, e


@functools.partial(jax.jit, static_argnames=("mm",))
def _experts(x, h, idx, wt, we_g, we_u, we_d, ws, mm):
    """x + the routed experts (expert e on the tokens idx[e], weighted
    by wt[e]; padding slots weigh 0) + the shared experts."""
    B, S, D = x.shape

    def one(a):
        i, w, g, u, d = a
        return swiglu(h[i], g, u, d, mm) * w[:, None]

    out = lax.map(one, (idx, wt, we_g, we_u, we_d))
    y = jnp.zeros((B * S, D), F32).at[idx.reshape(-1)].add(
        out.reshape(-1, D))
    return x + (y + swiglu(h, *ws, mm)).reshape(B, S, D)


def _assignments(top_w, top_e, E: int):
    """Per expert, the tokens routed to it and their weights, padded to
    one length (the power of two at or above the largest load, so that
    few lengths compile) with weight 0."""
    w, e = np.asarray(top_w), np.asarray(top_e)
    tok = [np.nonzero(e == x)[0] for x in range(E)]
    C = max(EXPERT_BUCKET, 1 << (max(len(t) for t in tok) - 1).bit_length())
    idx = np.zeros((E, C), np.int32)
    wt = np.zeros((E, C), np.float32)
    for x, t in enumerate(tok):
        idx[x, :len(t)] = t
        wt[x, :len(t)] = w[t, np.nonzero(e[t] == x)[1]]
    return jnp.asarray(idx), jnp.asarray(wt)


# ---------------------------------------------------------------- forward
def hidden(cfg, theta, tokens, mm: str, step_seed=0, active=None,
           scales=()):
    """Final-normed hidden states (B, S, D) f32 of tokens (B, S); the
    layers in ``active`` ({group: layers}) and the shared leaves read
    perturbed by ``scales`` when given."""
    eps = float(cfg["rms_norm_eps"])
    S = tokens.shape[1]
    cos, sin = (jnp.asarray(a) for a in rope_tables(cfg, S))
    dims = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])
    sc = softmax_scale(cfg)
    on, off = jnp.asarray(scales, F32), jnp.zeros((0,), F32)
    sh = {n: (_shared(theta[n], jnp.uint32(step_seed),
                      jnp.uint32(rng.uid(n)), on) if len(scales)
              else theta[n]) for n in ("embed/tok", "final_norm/scale")}
    x = sh["embed/tok"][tokens].astype(F32)
    for g, prefix, L, names in groups(cfg):
        paths = tuple(prefix + n for n in names)
        for layer in range(L):
            hit = active is not None and layer in active[g]
            w = dict(zip(names, _rows(tuple(theta[p] for p in paths),
                                      jnp.int32(layer), jnp.uint32(step_seed),
                                      on if hit else off, paths)))
            x = _mla(x, tuple(w[n] for n in MIX), cos, sin,
                     cfg["num_attention_heads"], dims, eps, sc, mm)
            if prefix == DENSE:
                x = _dense_ffn(x, tuple(w[n] for n in DENSE_FFN), eps, mm)
                continue
            h, top_w, top_e = _route(x, w["ffn/norm/scale"], w["ffn/router"],
                                     cfg["num_experts_per_tok"],
                                     bool(cfg["norm_topk_prob"]), eps, mm)
            idx, wt = _assignments(top_w, top_e, cfg["n_routed_experts"])
            x = _experts(x, h, idx, wt, w["ffn/we_g"], w["ffn/we_u"],
                         w["ffn/we_d"],
                         (w["ffn/ws_g"], w["ffn/ws_u"], w["ffn/ws_d"]), mm)
    return rms(x, sh["final_norm/scale"], eps)


@functools.partial(jax.jit, static_argnames=("mm",))
def _masked_ce(h, head, labels, mm):
    lg = matmul(h, head, mm)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def loss(cfg, theta, batch, mm: str, step_seed=0, active=None, scales=()):
    """Mean cross-entropy over the positions where loss_mask is set."""
    tokens = jnp.asarray(batch["tokens"], jnp.int32)
    h = hidden(cfg, theta, tokens, mm, step_seed, active, scales)
    head = theta["head/w"]
    if len(scales):
        head = _shared(head, jnp.uint32(step_seed),
                       jnp.uint32(rng.uid("head/w")),
                       jnp.asarray(scales, F32))
    rows, cols = np.nonzero(np.asarray(batch["loss_mask"]) > 0)
    labels = np.asarray(batch["labels"])[rows, cols]
    return float(_masked_ce(h[jnp.asarray(rows), jnp.asarray(cols)], head,
                            jnp.asarray(labels, jnp.int32), mm))


# --------------------------------------------------------------- ZO step
def drops(cfg: Dict, n_drop: int, step: int) -> Dict[str, int]:
    """Layers each group drops at ``step``: n_drop * L_g / N rounded
    down, and the leftover drops given by the low-discrepancy schedule
    over the step index.  The fractional shares, as numerators over N,
    lie end to end; the step's points u + j N (j below the leftover
    count) each give one more drop to the group whose share holds it,
    with u = step * a mod N for the stride a: with two shares the
    first one's numerator, else the number coprime to N nearest
    0.618 N."""
    sizes = [(g, L) for g, _, L, _ in groups(cfg)]
    N = sum(L for _, L in sizes)
    base = {g: n_drop * L // N for g, L in sizes}
    frac = [n_drop * L % N for _, L in sizes]
    if not any(frac):
        return base
    ends = list(np.cumsum(frac))
    shares = [f for f in frac if f]
    a = shares[0] if len(shares) == 2 else min(
        (c for c in range(1, N) if math.gcd(c, N) == 1),
        key=lambda c: abs(c - 0.618 * N))
    u = (step % N) * a % N
    lo = 0
    for (g, _), f, hi in zip(sizes, frac, ends):
        # points of u + jN in [lo, hi)
        base[g] += -((u - hi) // N) + ((u - lo) // N) if f else 0
        lo = hi
    return base


def select(cfg: Dict, step_seed: int, step: int, n_drop: int) -> Dict[str, Tuple[int, ...]]:
    """LeZO's kept layers per group: rank a group's layers by hashed bits
    of the step seed and drop its ``drops`` lowest, ties in layer order."""
    out, d = {}, drops(cfg, n_drop, step)
    for g, _, L, _ in groups(cfg):
        gseed = rng.fold_py(step_seed, rng.uid("sel/" + g))
        bits = [opt._mix_py((i * 0x9E3779B9 + gseed) & rng.MASK32)
                for i in range(L)]
        order = np.argsort(np.asarray(bits, np.uint64), kind="stable")
        out[g] = tuple(sorted(int(i) for i in order[d[g]:]))
    return out


def zo_step(cfg, theta, batch, step_seed: int, step: int, *, eps: float,
            lr: float, n_drop: int, mm: str, g_applied=None, skip_update=()):
    """One two-point LeZO step on ``theta`` (updated in place), as in
    ``reference/opt.py``; the leaves named in ``skip_update`` (names
    within a layer, as ``MOE_FFN``'s) are restored but not updated.
    Returns ((l+, l-), projected gradient, kept layers per group)."""
    active = select(cfg, step_seed, step, n_drop)
    e, e2 = np.float32(eps), np.float32(-2.0 * eps)
    # +eps as (eps, 0): the same writes, and one compiled shape for both
    l_plus = loss(cfg, theta, batch, mm, step_seed, active, (e, np.float32(0)))
    l_minus = loss(cfg, theta, batch, mm, step_seed, active, (e, e2))
    g = np.float32((np.float32(l_plus) - np.float32(l_minus))
                   / np.float32(2.0 * eps))
    ga = g if g_applied is None else np.float32(g_applied)
    write(cfg, theta, step_seed, active,
          [e, e2, np.float32(e - np.float32(lr) * ga)],
          {n: [e, e2, e] for n in skip_update})
    return (float(l_plus), float(l_minus)), float(g), active


def write(cfg, theta, step_seed: int, active, scales, by_name=None) -> None:
    """A step's writes on ``theta`` (in place): each kept layer's rows
    and every unstacked leaf, x <- type(x + s z) for each s of
    ``scales`` in turn (``by_name[n]`` in place of ``scales`` for the
    stacked leaves named n within a layer)."""
    where = {prefix: g for g, prefix, _, _ in groups(cfg)}
    for path, *_ in layout(cfg):
        prefix = path[:len(DENSE)]
        uid = jnp.uint32(rng.uid(path))
        sc = jnp.asarray((by_name or {}).get(path[len(DENSE):], scales), F32)
        if prefix in where:
            for layer in active[where[prefix]]:
                theta[path] = _write_layer(theta[path], jnp.int32(layer),
                                           jnp.uint32(step_seed), uid, sc)
        else:
            theta[path] = _shared(theta[path], jnp.uint32(step_seed), uid, sc)


def intended(lr: float, g: float) -> float:
    """The scale a step's update applies along its z: -lr g, as the
    step computes it in float32."""
    return -float(np.float32(lr) * np.float32(g))


def zo_readings(cfg, seed: int, batches, base_seed: int, *, eps, lr, n_drop,
                mm: str, half_batch: bool = False, grads=None,
                skip_update=()) -> Dict:
    """``reference/opt.py``'s readings for this model: each step's probe
    losses and projected gradient, and each leaf's share of the steps'
    updates (``applied_steps``) after the first step and after the last;
    with ``grads`` each update applies the given projected gradient, so
    the reference follows that run; ``skip_update`` as ``zo_step``'s."""
    theta = make_weights(cfg, seed)
    out = {"probes": [], "grad": [], "active": []}
    steps = []
    for t, batch in enumerate(batches):
        if half_batch:
            n = batch["tokens"].shape[0] // 2
            batch = {k: np.asarray(v)[:n] for k, v in batch.items()}
        step_seed = rng.fold_py(base_seed, t)
        ga = None if grads is None else grads[t]
        lv, g, act = zo_step(cfg, theta, batch, step_seed, t, eps=eps, lr=lr,
                             n_drop=n_drop, mm=mm, g_applied=ga,
                             skip_update=skip_update)
        out["probes"] += list(lv)
        out["grad"].append(g)
        out["active"].append(act)
        steps.append((step_seed, act, intended(lr, g if ga is None else ga)))
        if t == 0:
            out["change1"] = applied_steps(cfg, seed, theta, steps)
    out["change_last"] = applied_steps(cfg, seed, theta, steps)
    del theta
    return out
