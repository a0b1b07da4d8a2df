"""LeZO / MeZO optimizer core: layer-sparse SPSA + ZO-SGD over pytrees.

The optimizer sees parameters through a :class:`ZOSpec`, which labels each
leaf as either *always-perturbed* (embeddings, head, final norm, PEFT
vectors) or *stacked over a layer group* (axis 0 = layers of one
homogeneous block group — see models.lm).

A single ZO step (Algorithm 1 of the paper)::

    active  = select(seed_t)                       # LeZO subset
    theta  += eps * z        (on active layers)    # perturb +
    l_plus  = loss(theta)
    theta  -= 2*eps * z                            # perturb -
    l_minus = loss(theta)
    g       = (l_plus - l_minus) / (2*eps)         # projected grad (scalar!)
    theta  += (eps - lr*g) * z                     # fused restore+update

Every pass regenerates z from (base_seed, step); nothing is stored, and
under data parallelism the only cross-replica values are the two scalar
losses.  ``fused_update=False`` gives the paper-faithful separate
restore + update passes.

Since the estimator refactor (DESIGN.md §6) this module owns the ZOSpec
/ selection / axpy plumbing while the gradient estimate itself lives in
``repro.estimators`` — :func:`make_zo_step` is a compatibility shim over
the ``two_point`` estimator, and FZOO-style batched one-sided, averaged
multi-direction, and importance-weighted estimators are one config away
(``estimators.make_step``).

Layer selection
---------------
``policy="uniform"`` is the paper's policy: drop n_drop of the N global
layers uniformly.  ``policy="stratified"`` (default here) fixes a static
per-group quota (largest-remainder apportionment of n_drop over groups)
and samples uniformly *within* each group — statistically equivalent for
single-group models (i.e. all of the paper's OPT experiments) and
required by the ``gather`` backend, whose compact active buffer needs a
static size per stacked leaf.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import rng, selection
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import trace as obs


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class ZOSpec:
    """Maps parameter leaves to layer groups (see build_spec)."""
    paths: Tuple[str, ...]
    groups: Tuple[Optional[str], ...]
    slices: Dict[str, Tuple[int, int]]   # group -> (start, length) globally
    num_layers: int

    def split_mask(self, active):
        return {g: jax.lax.dynamic_slice(active, (s,), (l,))
                for g, (s, l) in self.slices.items()}

    def quotas(self, n_drop: int) -> Dict[str, int]:
        """Largest-remainder apportionment of n_drop over groups."""
        if self.num_layers == 0:
            # No stacked groups (e.g. a flat toy tree): nothing to drop.
            if n_drop:
                raise ValueError("n_drop > 0 but the spec has no layer groups")
            return {}
        if not 0 <= n_drop < self.num_layers:
            raise ValueError(f"n_drop must be in [0, {self.num_layers})")
        exact = {g: n_drop * L / self.num_layers
                 for g, (_, L) in self.slices.items()}
        base = {g: min(int(e), self.slices[g][1]) for g, e in exact.items()}
        order = sorted(exact, key=lambda g: exact[g] - base[g], reverse=True)
        i = 0
        while sum(base.values()) < n_drop:
            g = order[i % len(order)]
            if base[g] < self.slices[g][1]:
                base[g] += 1
            i += 1
        return base


def build_spec(params, group_fn: Callable[[str], Optional[str]]) -> ZOSpec:
    """``group_fn(path_str)`` returns the layer-group name for a leaf
    stacked over layers on axis 0, or None for always-perturbed leaves."""
    leaves = jax.tree_util.tree_leaves_with_path(params)
    paths, groups, sizes = [], [], {}
    for path, leaf in leaves:
        ps = _path_str(path)
        g = group_fn(ps)
        paths.append(ps)
        groups.append(g)
        if g is not None:
            L = leaf.shape[0]
            if sizes.setdefault(g, L) != L:
                raise ValueError(
                    f"group {g!r}: inconsistent layer counts {sizes[g]} vs {L} at {ps}")
    slices, start = {}, 0
    for g in sorted(sizes):
        slices[g] = (start, sizes[g])
        start += sizes[g]
    return ZOSpec(tuple(paths), tuple(groups), slices, start)


# ----------------------------------------------------------- selection
def _group_rank_bits(seed, salt: str, g: str, L: int):
    """Seeded per-layer ranking bits for group ``g`` — the one hashing
    scheme shared by the uniform and weighted stratified policies."""
    gseed = rng.fold(seed, jnp.uint32(rng.leaf_uid(salt + g)))
    ids = jnp.arange(L, dtype=jnp.uint32)
    return rng.mix32(ids * jnp.uint32(0x9E3779B9) + gseed)


def _mask_from_active(act, L: int):
    return jnp.zeros((L,), jnp.bool_).at[act].set(True)


def stratified_select(spec: ZOSpec, seed, n_drop: int):
    """Per-group masks + static-size active index vectors.

    Returns (masks: {g: (L_g,) bool}, idxs: {g: (L_g - quota_g,) int32},
    n_active).
    """
    quotas = spec.quotas(n_drop)
    masks, idxs = {}, {}
    n_active = 0
    for g, (start, L) in spec.slices.items():
        q = quotas[g]
        bits = _group_rank_bits(seed, "sel/", g, L)
        order = jnp.argsort(bits)
        act = jnp.sort(order[q:]).astype(jnp.int32)      # active, ascending
        masks[g] = _mask_from_active(act, L)
        idxs[g] = act
        n_active += L - q
    return masks, idxs, n_active


def stratified_select_weighted(spec: ZOSpec, seed, n_drop: int, weights):
    """Importance-weighted LeZO selection with static per-group quotas.

    ``weights`` (num_layers,) >= 0, globally indexed like ZOSpec.slices.
    Gumbel top-k by log-weight within each group: heavier layers are kept
    more often, selection stays fully stochastic (every layer has nonzero
    keep probability), and the per-group active count is the same static
    ``L_g - quota_g`` as :func:`stratified_select`, so the gather
    backend's compact buffers keep their shapes.  Uniform weights recover
    the unweighted distribution.
    """
    quotas = spec.quotas(n_drop)
    masks, idxs = {}, {}
    n_active = 0
    for g, (start, L) in spec.slices.items():
        k = L - quotas[g]
        w = jax.lax.dynamic_slice(jnp.asarray(weights, jnp.float32),
                                  (start,), (L,))
        bits = _group_rank_bits(seed, "wsel/", g, L)
        u = jnp.clip((bits >> jnp.uint32(8)).astype(jnp.float32)
                     / jnp.float32(1 << 24), 1e-7, 1.0 - 1e-7)
        gumbel = -jnp.log(-jnp.log(u))
        score = jnp.log(jnp.clip(w, 1e-9, None)) + gumbel
        order = jnp.argsort(-score)
        act = jnp.sort(order[:k]).astype(jnp.int32)      # active, ascending
        masks[g] = _mask_from_active(act, L)
        idxs[g] = act
        n_active += k
    return masks, idxs, n_active


def uniform_select(spec: ZOSpec, seed, n_drop: int):
    """Paper policy: global uniform drop (dynamic per-group counts)."""
    active = selection.uniform_active(seed, spec.num_layers, n_drop)
    return spec.split_mask(active), None, spec.num_layers - n_drop


# ----------------------------------------------------------------- axpy
def tree_axpy(params, spec: ZOSpec, seed, scale, masks, idxs=None, *,
              decay=1.0, backend="dense"):
    """theta <- decay*theta + scale*z on active layers, identity elsewhere."""
    obs.get_tracer().count(obs.CTR_AXPY)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    assert len(leaves) == len(spec.paths), "params tree changed since build_spec"
    out = []
    for leaf, path, group in zip(leaves, spec.paths, spec.groups):
        mask = None if group is None else masks[group]
        aidx = None if (group is None or idxs is None) else idxs[group]
        out.append(kops.zo_axpy(
            leaf, path=path, seed=seed, scale=scale, decay=decay,
            mask=mask, active_idx=aidx, backend=backend))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------- health / norm identity
def global_layer_mask(spec: ZOSpec, masks):
    """Per-group masks -> one (num_layers,) bool at the global indices."""
    gmask = jnp.zeros((spec.num_layers,), jnp.bool_)
    for g, (start, _) in spec.slices.items():
        gmask = jax.lax.dynamic_update_slice(gmask, masks[g], (start,))
    return gmask


def leaf_shapes(params) -> Tuple[Tuple[int, ...], ...]:
    """Static leaf shapes in ``ZOSpec.paths`` order (jit-safe input to
    :func:`active_param_count` / :func:`tree_z_norm`)."""
    return tuple(tuple(leaf.shape)
                 for leaf in jax.tree_util.tree_leaves(params))


def active_param_count(spec: ZOSpec, shapes, masks):
    """f32 count of parameters one direction's z touches: full sizes for
    always-perturbed leaves + mask-selected rows of stacked leaves.
    Float because 13B-scale counts overflow int32; exact up to 2^24 per
    leaf times active layers, plenty for the E‖z‖² = N norm estimate."""
    total = jnp.float32(0.0)
    for shape, group in zip(shapes, spec.groups):
        if group is None:
            total = total + jnp.float32(math.prod(shape))
        else:
            per_layer = float(math.prod(shape[1:]))
            total = total + jnp.sum(
                masks[group].astype(jnp.float32)) * jnp.float32(per_layer)
    return total


def tree_z_norm(spec: ZOSpec, shapes, seed, masks):
    """Exact ‖z(seed)‖ over the active subset — the RNG-stream norm
    identity: z is a pure function of (seed, leaf, layer, element), so
    the magnitude of the update ``-lr·g·z`` a recorded step applied is
    ``|lr·g| * tree_z_norm(...)`` without ever materializing z alongside
    the parameters.  Regenerates each leaf's stream exactly as
    ``kernels/ops.zo_axpy`` does (same fold(seed, leaf_uid) keying,
    single pseudo-layer for ungrouped leaves)."""
    seed = jnp.asarray(seed, jnp.uint32)
    total = jnp.float32(0.0)
    for shape, path, group in zip(shapes, spec.paths, spec.groups):
        leaf_seed = rng.fold(seed, jnp.uint32(rng.leaf_uid(path)))
        if group is None:
            z = kref.leaf_normal_nd(leaf_seed, (1,) + tuple(shape))
            total = total + jnp.sum(z * z)
        else:
            z = kref.leaf_normal_nd(leaf_seed, tuple(shape))
            m = masks[group].astype(jnp.float32).reshape(
                (shape[0],) + (1,) * (len(shape) - 1))
            total = total + jnp.sum(m * z * z)
    return jnp.sqrt(total)


@dataclasses.dataclass(frozen=True)
class ZOConfig:
    eps: float = 1e-3
    lr: float = 1e-6
    n_drop: int = 0               # 0 => MeZO; >0 => LeZO
    policy: str = "stratified"    # stratified | uniform
    backend: str = "dense"        # dense | scan | gather | pallas
    fused_update: bool = True     # beyond-paper single restore+update pass
    weight_decay: float = 0.0
    # materialized = classic perturb/forward/restore sweeps;
    # virtual[_ref] = fused forward regenerates z in-kernel, the step is
    # 2 forwards + 1 update axpy with zero perturb/restore writes
    # (repro.fused, DESIGN.md §10)
    forward_backend: str = "materialized"
    # stack the virtual ±εz pair onto ONE paired forward (each W tile
    # loaded and each z tile regenerated once per pair) — bit-identical
    # to the per-probe virtual path; ignored when materialized
    paired_probes: bool = True


def make_zo_step(loss_fn: Callable, spec: ZOSpec, cfg: ZOConfig,
                 lr_schedule: Optional[Callable] = None):
    """Build the jit-able ZO step: step(params, batch, step_idx, base_seed)
    -> (params, metrics).  ``loss_fn(params, batch) -> scalar`` must
    average over the (possibly sharded) batch.  Donate params at jit time.

    Since the estimator refactor this is a thin shim over the two-point
    estimator in ``repro.estimators`` — the probe/update op sequence (and
    therefore every result bit) is unchanged from the original inline
    implementation; tests/test_estimators.py holds the line.  Callers who
    want a different estimator (one_sided, averaged, importance) use
    ``estimators.make_step`` directly, which also threads estimator state.
    """
    if cfg.backend == "gather" and cfg.policy != "stratified":
        raise ValueError("gather backend requires the stratified policy")
    from repro import estimators  # local import: estimators builds on zo

    ecfg = estimators.from_zo(cfg)
    estep, _ = estimators.make_step(loss_fn, spec, ecfg, lr_schedule)

    def step(params, batch, step_idx, base_seed):
        p, _state, metrics = estep(params, {}, batch, step_idx, base_seed)
        return p, metrics

    return step
