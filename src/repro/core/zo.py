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
layers uniformly.  ``policy="stratified"`` (default here) apportions
n_drop over the groups and samples uniformly *within* each group —
statistically equivalent for single-group models (i.e. all of the
paper's OPT experiments).  A group of L_g layers is owed
n_drop * L_g / N drops a step: it always takes the integer part, and
the leftover drops (as many as the fractional parts sum to) go to the
groups by a low-discrepancy schedule over the step index
(:meth:`ZOSpec.quotas`), so every layer of every group is active with
probability (N - n_drop) / N and the step's total stays N - n_drop.
Each group's active index vector keeps a static length (its largest
active count) for the in-place row sweep (``kernels/ops.zo_axpy``); in a
step that drops one more, its last slot is disabled.
"""
from __future__ import annotations

import dataclasses
import itertools
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

    def quota_parts(self, n_drop: int):
        """The static part of the apportionment: {g: int(n_drop * L_g / N)}
        and {g: the fractional part's numerator over N}."""
        if self.num_layers == 0:
            # No stacked groups (e.g. a flat toy tree): nothing to drop.
            if n_drop:
                raise ValueError("n_drop > 0 but the spec has no layer groups")
            return {}, {}
        if not 0 <= n_drop < self.num_layers:
            raise ValueError(f"n_drop must be in [0, {self.num_layers})")
        N = self.num_layers
        base = {g: n_drop * L // N for g, (_, L) in self.slices.items()}
        frac = {g: n_drop * L % N for g, (_, L) in self.slices.items()}
        return base, frac

    def quotas(self, n_drop: int, step=None) -> Dict[str, Any]:
        """Drops per group at ``step``: the integer part of each group's
        share, plus the leftover drops placed by a low-discrepancy
        schedule.

        The fractional parts, as numerators over N, lie end to end on
        [0, r*N) (r leftover drops); the step's r points u_t + j*N,
        j < r, give one more drop to each group whose interval holds
        one (an interval is shorter than N, so it holds at most one).
        u_t = t * a mod N (``_stride``) visits the multiples of
        gcd(a, N) evenly and every interval end is one of them, so each
        group's rate is exact; with two groups each group's count in
        every window of W steps is within +-1 of its expectation.
        Groups with no fractional part get Python ints; the others
        traced int32 unless ``step`` is a Python int.  ``step`` may be
        left out only where no group has a fractional part."""
        base, frac = self.quota_parts(n_drop)
        if not any(frac.values()):
            return base
        if step is None:
            raise ValueError("groups whose share of n_drop is fractional "
                             "need the step index")
        N = self.num_layers
        ends = list(itertools.accumulate(frac[g] for g in self.slices))
        if not isinstance(step, int):
            step = jnp.asarray(step, jnp.int32)
        u = (step % N) * _stride(N, frac) % N
        ceil_div = lambda x: -((u - x) // N)
        out, lo = {}, 0
        for g, hi in zip(self.slices, ends):
            out[g] = base[g] + (ceil_div(hi) - ceil_div(lo) if frac[g] else 0)
            lo = hi
        return out


def _stride(N: int, frac: Dict[str, int]) -> int:
    """The schedule's stride.  With two fractional shares (n and N - n),
    a = n: each group's drops then follow a Bresenham line.  With more,
    the stride coprime to N nearest N / golden ratio, which spreads
    every share's points evenly."""
    shares = [f for f in frac.values() if f]
    if len(shares) == 2:
        return shares[0]
    return min((a for a in range(1, N) if math.gcd(a, N) == 1),
               key=lambda a: abs(a - 0.618 * N))


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


def _slots(ranked, k_max: int, n_off, L: int):
    """Active index slots from ``ranked`` (layer ids, most-kept first):
    the first k_max - n_off of ranked[:k_max], ascending.  With a
    traced ``n_off`` the vector keeps length k_max: the disabled slots
    come last and hold L (out of range: masks drop it), and the slots
    travel as (vector, enabled count)."""
    cand = ranked[:k_max]
    if isinstance(n_off, int):
        return jnp.sort(cand[:k_max - n_off]).astype(jnp.int32)
    off = jnp.arange(k_max) >= k_max - n_off
    idx = jnp.sort(jnp.where(off, L, cand)).astype(jnp.int32)
    return idx, (k_max - n_off).astype(jnp.int32)


def _select(spec: ZOSpec, n_drop: int, step, rank_fn):
    """Masks, active slots and the step's active count, group by group:
    ``rank_fn(g, start, L)`` ranks a group's layers, most-kept first."""
    base, _ = spec.quota_parts(n_drop)
    drops = spec.quotas(n_drop, step=step)
    masks, idxs = {}, {}
    for g, (start, L) in spec.slices.items():
        slots = _slots(rank_fn(g, start, L), L - base[g],
                       drops[g] - base[g], L)
        idxs[g] = slots
        masks[g] = _mask_from_active(
            slots[0] if isinstance(slots, tuple) else slots, L)
    return masks, idxs, spec.num_layers - n_drop


def stratified_select(spec: ZOSpec, seed, n_drop: int, step=None):
    """Per-group masks + static-size active slots.

    Returns (masks: {g: (L_g,) bool}, idxs: {g: slots}, n_active), where
    a group's slots are its (k_g,) int32 active layers, ascending, or,
    for a group whose drop count varies by step (:meth:`ZOSpec.quotas`),
    a (vector, enabled count) pair (see ``_slots``).  ``step``, the
    step index, places the leftover drops (:meth:`ZOSpec.quotas`).
    """
    def rank(g, start, L):
        # drop the lowest-ranked layers: most-kept first is descending
        return jnp.argsort(_group_rank_bits(seed, "sel/", g, L))[::-1]

    return _select(spec, n_drop, step, rank)


def stratified_select_weighted(spec: ZOSpec, seed, n_drop: int, weights,
                               step=None):
    """Importance-weighted LeZO selection under the same quotas.

    ``weights`` (num_layers,) >= 0, globally indexed like ZOSpec.slices.
    Gumbel top-k by log-weight within each group: heavier layers are kept
    more often, selection stays fully stochastic (every layer has nonzero
    keep probability), and each group's slots have the same static
    length as :func:`stratified_select`'s.  Uniform weights recover the
    unweighted distribution.
    """
    def rank(g, start, L):
        w = jax.lax.dynamic_slice(jnp.asarray(weights, jnp.float32),
                                  (start,), (L,))
        bits = _group_rank_bits(seed, "wsel/", g, L)
        u = jnp.clip((bits >> jnp.uint32(8)).astype(jnp.float32)
                     / jnp.float32(1 << 24), 1e-7, 1.0 - 1e-7)
        gumbel = -jnp.log(-jnp.log(u))
        score = jnp.log(jnp.clip(w, 1e-9, None)) + gumbel
        return jnp.argsort(-score)

    return _select(spec, n_drop, step, rank)


def uniform_select(spec: ZOSpec, seed, n_drop: int):
    """Paper policy: global uniform drop.  A group's active count varies
    by step, so its slots are (its active layers ascending, padded to
    L_g with L_g; the enabled count) — the form of ``_slots``."""
    active = selection.uniform_active(seed, spec.num_layers, n_drop)
    masks = spec.split_mask(active)
    idxs = {}
    for g, m in masks.items():
        L = m.shape[0]
        ids = jnp.where(m, jnp.arange(L, dtype=jnp.int32), L)
        idxs[g] = jnp.sort(ids), jnp.sum(m, dtype=jnp.int32)
    return masks, idxs, spec.num_layers - n_drop


# ----------------------------------------------------------------- axpy
def tree_axpy(params, spec: ZOSpec, seed, scale, idxs, *, decay=1.0):
    """theta <- decay*theta + scale*z on the active rows ``idxs`` of
    each stacked leaf (a selection policy's slots), and over the whole
    of every leaf with no group."""
    obs.get_tracer().count(obs.CTR_AXPY)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    assert len(leaves) == len(spec.paths), "params tree changed since build_spec"
    out = []
    for leaf, path, group in zip(leaves, spec.paths, spec.groups):
        out.append(kops.zo_axpy(
            leaf, path=path, seed=seed, scale=scale, decay=decay,
            active_idx=None if group is None else idxs[group]))
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
    from repro import estimators  # local import: estimators builds on zo

    ecfg = estimators.from_zo(cfg)
    estep, _ = estimators.make_step(loss_fn, spec, ecfg, lr_schedule)

    def step(params, batch, step_idx, base_seed):
        p, _state, metrics = estep(params, {}, batch, step_idx, base_seed)
        return p, metrics

    return step
