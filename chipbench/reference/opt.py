"""Plain reference of OPT as the program runs it, and of one ZO step.

The model: token and learned position embeddings, pre-LayerNorm blocks
(LayerNorm, q/k/v/o attention with a causal softmax, residual;
LayerNorm, ReLU MLP, residual), a final LayerNorm, and a head tied to
the token table.  Departures from the published OPT, which the program
makes and the configuration files state under ``assumed``: no bias on
the projections, a 2048-row position table with no offset of 2,
bfloat16 weights.

Everything is computed in float32 with matmuls at ``HIGHEST`` precision,
one layer at a time, so that a model that fills the chip's memory in
bfloat16 still fits beside the reference's activations.  ``mm="fp8"``
computes every matmul from operands rounded to float8 (e4m3, a scale per
row of activations and per weight matrix): the precision below the
configuration's bfloat16, and the control that the comparison has to
fail.

The weights are the benchmark's own: every leaf is drawn elementwise
from the counter RNG (``rng``) under ``rng.weight_seed(seed)``, in the
parameter layout the program's OPT uses, so the program and the
reference are handed equal weights without either taking them from the
other.  The ZO step follows the two-point estimator with LeZO layer
selection: perturb +eps, loss, perturb -2 eps, loss, one fused restore
and update, every perturbation rounded to the leaf's type as it is
written.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from reference import rng

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-5
STAGE = "stages/s0/b0/"
FP8_MAX = 448.0


# ---------------------------------------------------------------- layout
def dims(cfg: Dict) -> Tuple[int, int, int, int, int, int]:
    return (cfg["hidden_size"], cfg["ffn_dim"], cfg["num_attention_heads"],
            cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["max_position_embeddings"])


def layout(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str, str, float]]:
    """(path, shape, dtype, kind, std) of every leaf, in path order.
    Stacked leaves (under ``stages/``) carry the layer axis first; kind
    is ``normal`` (std * N), ``scale`` (1 + std * N) or ``bias``."""
    D, Fd, H, L, V, P = dims(cfg)
    dt = "bfloat16" if cfg["assumed"]["dtype"] == "bfloat16" else "float32"
    leaves = [
        ("embed/pos", (P, D), dt, "normal", 0.02),
        ("embed/tok", (V, D), dt, "normal", 0.02),
        ("final_norm/bias", (D,), "float32", "bias", 0.1),
        ("final_norm/scale", (D,), "float32", "scale", 0.1),
        (STAGE + "ffn/norm/bias", (L, D), "float32", "bias", 0.1),
        (STAGE + "ffn/norm/scale", (L, D), "float32", "scale", 0.1),
        (STAGE + "ffn/wd", (L, Fd, D), dt, "normal", Fd ** -0.5),
        (STAGE + "ffn/wi", (L, D, Fd), dt, "normal", D ** -0.5),
        (STAGE + "mix/norm/bias", (L, D), "float32", "bias", 0.1),
        (STAGE + "mix/norm/scale", (L, D), "float32", "scale", 0.1),
        (STAGE + "mix/wk", (L, D, D), dt, "normal", D ** -0.5),
        (STAGE + "mix/wo", (L, D, D), dt, "normal", D ** -0.5),
        (STAGE + "mix/wq", (L, D, D), dt, "normal", D ** -0.5),
        (STAGE + "mix/wv", (L, D, D), dt, "normal", D ** -0.5),
    ]
    return leaves


def stacked(path: str) -> bool:
    return path.startswith("stages/")


def _leaf_values(wseed, path, shape, dtype, kind, std):
    lseed = rng.fold(jnp.uint32(wseed), jnp.uint32(rng.uid(path)))
    full = tuple(shape) if stacked(path) else (1,) + tuple(shape)
    seeds = rng.fold(lseed, jnp.arange(full[0], dtype=jnp.uint32))
    seeds = seeds.reshape((full[0],) + (1,) * (len(full) - 1))
    n = rng.normal(seeds, rng.layer_index(full)) * np.float32(std)
    if kind == "scale":
        n = n + 1.0
    n = n if stacked(path) else n[0]
    return n.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _leaf_jit(wseed, path, shape, dtype, kind, std):
    return _leaf_values(wseed, path, shape, dtype, kind, std)


@functools.partial(jax.jit, static_argnums=(0,))
def _all_leaves(lay, wseed):
    return {p: _leaf_values(wseed, p, s, dt, k, sd) for p, s, dt, k, sd in lay}


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every leaf of the model for run seed ``seed``, in one jitted call
    on the default device; path -> array."""
    return _all_leaves(tuple(layout(cfg)), jnp.uint32(rng.weight_seed(seed)))


def weight_leaf(cfg: Dict, seed: int, path: str) -> jax.Array:
    (entry,) = [e for e in layout(cfg) if e[0] == path]
    return _leaf_jit(jnp.uint32(rng.weight_seed(seed)), *entry)


def to_tree(flat: Dict[str, jax.Array]) -> Dict:
    """path -> array  into the nested dict the program's model takes."""
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def from_tree(tree, prefix: str = "") -> Dict[str, jax.Array]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(from_tree(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


@jax.jit
def _change_norm(theta, theta0):
    d = theta.astype(F32) - theta0.astype(F32)
    return jnp.sqrt(jnp.sum(d * d))


def change_norms(cfg: Dict, seed: int, flat: Dict[str, jax.Array]) -> Dict[str, float]:
    """Per leaf, the norm of ``flat`` minus the weights the run started
    from, regenerated one leaf at a time so that it fits beside them."""
    out = {}
    for path, *_ in layout(cfg):
        theta0 = weight_leaf(cfg, seed, path)
        out[path] = float(_change_norm(flat[path], theta0))
        del theta0
    return out


# ---------------------------------------------------------------- matmuls
def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def matmul(a, w, mm: str):
    """a (..., K) @ w (K, N) in float32; ``fp8`` rounds both operands
    to float8 first (per row of ``a``, per matrix ``w``)."""
    a, w = a.astype(F32), w.astype(F32)
    if mm == "fp8":
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def _einsum(spec, x, y, mm: str):
    if mm == "fp8":
        x, y = _q8(x, -1), _q8(y, -1)
    return jnp.einsum(spec, x, y, precision=HIGHEST)


def layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * scale.astype(F32) + bias.astype(F32)


def attention(q, k, v, q_pos, mm: str):
    """Causal softmax attention of one sequence: q (Sq, H, dh) at
    absolute positions q_pos, k/v (Sk, H, dh) at positions 0 .. Sk-1."""
    dh = q.shape[-1]
    s = _einsum("qhd,khd->hqk", q, k, mm) * (dh ** -0.5)
    keep = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # keys last in both operands, so fp8 scales run along the contraction
    return _einsum("hqk,hdk->qhd", p, jnp.transpose(v, (1, 2, 0)), mm)


# ------------------------------------------------------ perturbed weights
def perturb(x, z, scales):
    """The step's writes in order: x <- type(x + s z) for each s of the
    (traced) f32 vector ``scales``."""
    for i in range(scales.shape[0]):
        x = (x.astype(F32) + scales[i] * z).astype(x.dtype)
    return x


def _z(step_seed, path, layer, shape):
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), jnp.uint32(rng.uid(path)))
    return rng.layer_normals(lseed, layer, shape)


LAYER_LEAVES = ("mix/norm/scale", "mix/norm/bias", "mix/wq", "mix/wk",
                "mix/wv", "mix/wo", "ffn/norm/scale", "ffn/norm/bias",
                "ffn/wi", "ffn/wd")


@functools.partial(jax.jit, static_argnames=("heads", "mm"))
def _layer(x, q_pos, leaves, layer, step_seed, scales, heads, mm):
    """One block on x (B, S, D) f32.  ``leaves`` are the stacked leaves
    in LAYER_LEAVES order; with ``scales`` the layer is read perturbed
    by z(step_seed) as the step writes it."""
    w = {}
    for name, leaf in zip(LAYER_LEAVES, leaves):
        row = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
        if scales.shape[0]:
            row = perturb(row, _z(step_seed, STAGE + name, layer, row.shape),
                          scales)
        w[name] = row.astype(F32)
    B, S, D = x.shape
    H = heads
    dh = D // H
    h = layer_norm(x, w["mix/norm/scale"], w["mix/norm/bias"])
    q = matmul(h, w["mix/wq"], mm).reshape(B, S, H, dh)
    k = matmul(h, w["mix/wk"], mm).reshape(B, S, H, dh)
    v = matmul(h, w["mix/wv"], mm).reshape(B, S, H, dh)
    o = lax.map(lambda a: attention(a[0], a[1], a[2], q_pos, mm), (q, k, v))
    x = x + matmul(o.reshape(B, S, D), w["mix/wo"], mm)
    h = layer_norm(x, w["ffn/norm/scale"], w["ffn/norm/bias"])
    return x + matmul(jax.nn.relu(matmul(h, w["ffn/wi"], mm)), w["ffn/wd"], mm)


@jax.jit
def _shared(leaf, step_seed, path_uid, scales):
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), jnp.uint32(path_uid))
    z = rng.layer_normals(lseed, 0, leaf.shape)
    return perturb(leaf, z, scales)


def _shared_leaves(theta, step_seed, scales):
    names = ("embed/tok", "embed/pos", "final_norm/scale", "final_norm/bias")
    if not len(scales):
        return {n: theta[n] for n in names}
    return {n: _shared(theta[n], jnp.uint32(step_seed), jnp.uint32(rng.uid(n)),
                       jnp.asarray(scales, F32)) for n in names}


def hidden(cfg, theta, tokens, q_pos, mm: str, step_seed=0, active=(),
           scales=()):
    """Final-normed hidden states (B, S, D) f32 of tokens (B, S) at
    positions q_pos (S,); layers in ``active`` (and the shared leaves)
    read perturbed by ``scales`` when given."""
    sh = _shared_leaves(theta, step_seed, scales)
    x = (sh["embed/tok"][tokens].astype(F32)
         + sh["embed/pos"][q_pos].astype(F32)[None])
    leaves = tuple(theta[STAGE + n] for n in LAYER_LEAVES)
    on, off = jnp.asarray(scales, F32), jnp.zeros((0,), F32)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(x, q_pos, leaves, jnp.int32(layer), jnp.uint32(step_seed),
                   on if layer in active else off,
                   cfg["num_attention_heads"], mm)
    x = layer_norm(x, sh["final_norm/scale"], sh["final_norm/bias"])
    return x, sh["embed/tok"]


@functools.partial(jax.jit, static_argnames=("mm",))
def _logits(h, table, mm):
    return matmul(h, table.T, mm)


def logits_at(cfg, theta, tokens: np.ndarray, positions: np.ndarray,
              mm: str, bucket: int = 256) -> jax.Array:
    """Logits (n, V) f32 of one sequence ``tokens`` (S,) at the given
    0-based positions: the prediction of the token after each.  The
    sequence is padded at its end to a multiple of ``bucket`` (causal
    attention keeps padding out of every earlier position), so a few
    compiled shapes serve every length."""
    S = tokens.shape[0]
    padded = np.zeros((-(-S // bucket) * bucket,), np.int32)
    padded[:S] = tokens
    h, table = hidden(cfg, theta, jnp.asarray(padded[None]),
                      jnp.arange(padded.shape[0]), mm)
    return _logits(h[0][jnp.asarray(positions)], table, mm)


@functools.partial(jax.jit, static_argnames=("mm",))
def _masked_ce(h, table, labels, mask, mm):
    lg = matmul(h, table.T, mm)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss(cfg, theta, batch, mm: str, step_seed=0, active=(), scales=()):
    """Mean cross-entropy over the positions where loss_mask is set."""
    tokens = jnp.asarray(batch["tokens"], jnp.int32)
    h, table = hidden(cfg, theta, tokens, jnp.arange(tokens.shape[1]), mm,
                      step_seed, active, scales)
    m = np.asarray(batch["loss_mask"]) > 0
    rows, cols = np.nonzero(m)
    labels = np.asarray(batch["labels"])[rows, cols]
    return float(_masked_ce(h[jnp.asarray(rows), jnp.asarray(cols)], table,
                            jnp.asarray(labels, jnp.int32),
                            jnp.ones((len(rows),), F32), mm))


# --------------------------------------------------------------- ZO step
def select(step_seed: int, n_layers: int, n_drop: int) -> Tuple[int, ...]:
    """LeZO's kept layers: rank the layers by hashed bits of the step
    seed and drop the n_drop lowest, ties in layer order."""
    g = rng.fold_py(step_seed, rng.uid("sel/s0.b0"))
    ids = np.arange(n_layers, dtype=np.uint64)
    bits = (ids * 0x9E3779B9 + g) & rng.MASK32
    bits = np.asarray([_mix_py(int(b)) for b in bits], np.uint64)
    order = np.argsort(bits, kind="stable")
    return tuple(sorted(int(i) for i in order[n_drop:]))


def _mix_py(x: int) -> int:
    x ^= x >> 16
    x = (x * 0x7FEB352D) & rng.MASK32
    x ^= x >> 15
    x = (x * 0x846CA68B) & rng.MASK32
    return x ^ (x >> 16)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_layer(leaf, layer, step_seed, path_uid, scales):
    row = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    lseed = rng.fold(jnp.asarray(step_seed, jnp.uint32), jnp.uint32(path_uid))
    row = perturb(row, rng.layer_normals(lseed, layer, row.shape), scales)
    return lax.dynamic_update_index_in_dim(leaf, row, layer, 0)


def zo_step(cfg, theta, batch, step_seed: int, *, eps: float, lr: float,
            n_drop: int, mm: str, g_applied=None):
    """One two-point LeZO step on ``theta`` (path -> array, updated in
    place).  The update applies ``g_applied`` where given, else the
    step's own projected gradient.  Returns ((loss at +eps, loss at
    -eps), projected gradient, kept layers)."""
    active = select(step_seed, cfg["num_hidden_layers"], n_drop)
    e, e2 = np.float32(eps), np.float32(-2.0 * eps)
    l_plus = loss(cfg, theta, batch, mm, step_seed, active, (e,))
    l_minus = loss(cfg, theta, batch, mm, step_seed, active, (e, e2))
    g = np.float32((np.float32(l_plus) - np.float32(l_minus))
                   / np.float32(2.0 * eps))
    ga = g if g_applied is None else np.float32(g_applied)
    s3 = np.float32(np.float32(eps) - np.float32(lr) * ga)
    scales = jnp.asarray([e, e2, s3], F32)
    for path, *_ in layout(cfg):
        if stacked(path):
            for layer in active:
                theta[path] = _write_layer(theta[path], jnp.int32(layer),
                                           jnp.uint32(step_seed),
                                           jnp.uint32(rng.uid(path)), scales)
        else:
            theta[path] = _shared(theta[path], jnp.uint32(step_seed),
                                  jnp.uint32(rng.uid(path)), scales)
    return (float(l_plus), float(l_minus)), float(g), active


def zo_readings(cfg, seed: int, batches, base_seed: int, *, eps, lr, n_drop,
                mm: str, half_batch: bool = False, grads=None) -> Dict:
    """The reference's run of the first ``len(batches)`` steps from the
    run's weights: each step's two probe losses (flat, +eps first) and
    projected gradient, and each leaf's change after the first step and
    after the last.  With ``grads`` (one per step) each update applies
    the given projected gradient in place of the step's own: the
    reference then follows the trajectory of the run that reported them,
    as the serving check follows the served tokens, and each step's
    losses are read at that run's weights."""
    theta = make_weights(cfg, seed)
    out = {"probes": [], "grad": [], "active": []}
    for t, batch in enumerate(batches):
        if half_batch:
            n = batch["tokens"].shape[0] // 2
            batch = {k: np.asarray(v)[:n] for k, v in batch.items()}
        lv, g, act = zo_step(cfg, theta, batch, rng.fold_py(base_seed, t),
                             eps=eps, lr=lr, n_drop=n_drop, mm=mm,
                             g_applied=None if grads is None else grads[t])
        out["probes"] += list(lv)
        out["grad"].append(g)
        out["active"].append(list(act))
        if t == 0:
            out["change1"] = change_norms(cfg, seed, theta)
    out["change_last"] = change_norms(cfg, seed, theta)
    del theta
    return out
