"""Estimator protocol: regenerable update directions, never materialized.

A ZO gradient estimator probes the loss with seeded perturbations and
returns a :class:`DirectionSet` — q ``(seed, coefficient)`` pairs whose
implied parameter-space update is::

    theta <- decay * theta - lr * sum_i coeffs[i] * z(seeds[i])

Each ``z(seed_i)`` (and its LeZO layer subset) regenerates on the fly
from its seed via the counter RNG, exactly like the perturbation passes
themselves, so the optimizer state stays O(q) scalars regardless of the
model size — the invariant the whole repo is built around (DESIGN.md §6).

Implementations (see the sibling modules):

  * ``two_point``  — the paper's antithetic SPSA pair, extracted verbatim
                     from the pre-refactor ``core/zo.py`` step.
  * ``one_sided``  — FZOO-style: q one-sided probes against one shared
                     baseline loss, evaluated as a single vmapped
                     (widened) forward.
  * ``averaged``   — q independent two-point probes averaged; the update
                     replays q fused axpy passes (the ``zo_adaptive``
                     regenerate-from-seed trick).
  * ``importance`` — selection-policy wrapper: smoothed per-layer |g|
                     scores replace uniform layer drop.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import rng, zo
from repro.estimators import costs
from repro.obs import trace as obs

_DIR_SALT = 0xD16E  # folds the direction index into the step seed


@dataclasses.dataclass(frozen=True)
class EstimatorConfig:
    name: str = "two_point"       # two_point | one_sided | averaged | importance
    eps: float = 1e-3
    lr: float = 1e-6
    q: int = 1                    # directions per step (ignored by two_point)
    q_chunk: int = 0              # one_sided: probes vmapped per chunk
                                  # (0 = all q in one widened forward)
    n_drop: int = 0               # 0 => MeZO; >0 => LeZO layer sparsity
    policy: str = "stratified"    # stratified | uniform
    backend: str = "dense"        # dense | scan | gather | pallas
    fused_update: bool = True
    weight_decay: float = 0.0
    inner: str = "two_point"      # estimator the importance wrapper drives
    importance_decay: float = 0.99  # EMA for the per-layer |g| scores
    # materialized | virtual | virtual_ref — virtual probes evaluate
    # loss(theta + s*eps*z) through the fused forward (repro.fused): the
    # loss_fn must accept a ``perturb`` kwarg (models.lm.lm_loss does)
    # and the step performs zero perturb/restore parameter writes
    forward_backend: str = "materialized"
    # stack virtual probes onto ONE fused forward: two_point's ±εz pair
    # shares each W tile load *and* each z regeneration (shared seed);
    # one_sided's q-chunks share the W loads.  Bit-identical to the
    # per-probe virtual path (DESIGN.md §10); no effect when materialized
    paired_probes: bool = True


@dataclasses.dataclass
class DirectionSet:
    """q regenerable update directions — no perturbation pytree, ever.

    ``seeds``/``coeffs``: traced uint32 / f32 scalars per direction.
    ``restore``: static per-direction scale undoing the residual probe
    perturbation still sitting in the returned params (0.0 when the probe
    already restored; +eps for two-point's ``-eps`` exit state).
    ``masks``/``idxs``: per-direction layer subsets as returned by the
    selection policy — (L,) bools / static-size int32 vectors per group,
    themselves regenerable from the direction seed.
    """
    seeds: Tuple
    coeffs: Tuple
    restore: Tuple[float, ...]
    masks: Tuple
    idxs: Tuple

    def __len__(self):
        return len(self.seeds)


def direction_seeds(seed, q: int) -> Tuple:
    """Per-direction seeds.  Direction 0 keeps the step seed itself, so
    two_point — and averaged at q=1 — draw exactly the z the paper's step
    would; further directions fold in the direction index."""
    seed = jnp.asarray(seed, jnp.uint32)
    return (seed,) + tuple(
        rng.fold(seed, jnp.uint32(_DIR_SALT + i)) for i in range(1, q))


class Estimator:
    """Shared selection / axpy / update machinery for all estimators.

    ``select_fn(seed, state)`` overrides the layer-selection policy (the
    importance wrapper injects its weighted policy into the inner
    estimator this way); default is the config's uniform/stratified one.
    """
    name = "base"

    def __init__(self, spec: zo.ZOSpec, cfg: EstimatorConfig,
                 select_fn: Optional[Callable] = None):
        if (cfg.backend == "gather" and cfg.policy != "stratified"
                and select_fn is None and cfg.name != "importance"):
            raise ValueError("gather backend requires the stratified policy")
        self.spec, self.cfg = spec, cfg
        self._select = select_fn

    # -------------------------------------------------------- selection
    def select(self, seed, state):
        """-> (masks: {g: (L_g,) bool}, idxs: {g: (k_g,) int32} | None,
        n_active)."""
        if self._select is not None:
            sel = self._select(seed, state)
        elif self.cfg.policy == "stratified":
            sel = zo.stratified_select(self.spec, seed, self.cfg.n_drop)
        else:
            sel = zo.uniform_select(self.spec, seed, self.cfg.n_drop)
        tr = obs.get_tracer()
        if tr.enabled and not obs.tracing():
            tr.count(obs.CTR_SELECTS)
            tr.gauge(obs.GAUGE_ACTIVE, int(sel[2]))
        return sel

    # ------------------------------------------------------------ state
    def init_state(self) -> Dict:
        return {}

    def update_state(self, state, dirs: DirectionSet, metrics):
        return state

    # ------------------------------------------------------------- axpy
    def _ax(self, p, scale, seed, masks, idxs, decay=1.0, backend=None):
        return zo.tree_axpy(p, self.spec, seed, scale, masks, idxs,
                            decay=decay, backend=backend or self.cfg.backend)

    # -------------------------------------------------- virtual probing
    @property
    def virtual(self) -> bool:
        return self.cfg.forward_backend != "materialized"

    def _vloss(self, loss_fn, params, batch, seed, scale, masks):
        """Probe loss(theta + scale*z(seed)) with zero parameter writes:
        the fused forward regenerates z in its kernels (repro.fused)."""
        from repro import fused  # local: fused must stay import-light here
        ctx = fused.make_ctx(seed, scale, masks, self.cfg.forward_backend)
        return loss_fn(params, batch, perturb=ctx)

    def _vloss_pair(self, loss_fn, params, batch, seed, eps, masks):
        """The antithetic ±εz pair as ONE fused forward: returns the (2,)
        loss vector [l_plus, l_minus].  Same floats as two ``_vloss``
        calls at ±eps, but every W tile is loaded and every z tile
        regenerated once for the pair (fused.make_pair_ctx)."""
        from repro import fused
        ctx = fused.make_pair_ctx(seed, eps, masks,
                                  self.cfg.forward_backend)
        return loss_fn(params, batch, perturb=ctx)

    def _vloss_stack(self, loss_fn, params, batch, seeds, scales, masks):
        """P independent probes stacked onto one fused forward (one_sided's
        q-chunks): ``seeds`` (P,), ``scales`` scalar-or-(P,), ``masks``
        {g: (P, L_g)}.  Returns the (P,) loss vector — same floats as the
        vmapped per-probe path, one pass over W."""
        from repro import fused
        ctx = fused.make_stack_ctx(seeds, scales, masks,
                                   self.cfg.forward_backend)
        return loss_fn(params, batch, perturb=ctx)

    # --------------------------------------------------------- protocol
    def estimate(self, loss_fn, params, batch, seed, state):
        """Probe the loss.  -> (probed_params, DirectionSet, metrics).

        ``probed_params`` may still carry a residual perturbation (see
        DirectionSet.restore); callers either ``apply_update`` (which
        folds the restore into the update pass when possible) or
        ``restore_probe`` to get the unperturbed parameters back.
        """
        raise NotImplementedError

    def restore_probe(self, params, dirs: DirectionSet):
        for i, r in enumerate(dirs.restore):
            if r != 0.0:
                params = self._ax(params, r, dirs.seeds[i], dirs.masks[i],
                                  dirs.idxs[i])
        return params

    def apply_update(self, params, dirs: DirectionSet, lr, decay=1.0):
        """theta <- decay*theta - lr * sum_i coeffs[i] * z_i, as q fused
        axpy passes (restore folded into the single pass when q == 1)."""
        q = len(dirs)
        with obs.stage(obs.UPDATE) as sp:
            if self.cfg.fused_update and q == 1 and dirs.restore[0] != 0.0:
                return sp.fence(self._ax(
                    params, dirs.restore[0] - lr * dirs.coeffs[0],
                    dirs.seeds[0], dirs.masks[0], dirs.idxs[0], decay))
            params = self.restore_probe(params, dirs)
            for i in range(q):
                params = self._ax(params, -lr * dirs.coeffs[i], dirs.seeds[i],
                                  dirs.masks[i], dirs.idxs[i],
                                  decay if i == 0 else 1.0)
            return sp.fence(params)

    def step_counts(self) -> Dict:
        """Analytic per-step cost counts (see estimators/costs.py)."""
        return costs.step_counts(self.cfg.name, q=self.cfg.q,
                                 fused_update=self.cfg.fused_update,
                                 inner=self.cfg.inner,
                                 num_layers=self.spec.num_layers,
                                 forward_backend=self.cfg.forward_backend)
