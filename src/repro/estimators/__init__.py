"""Pluggable ZO gradient-estimator subsystem.

The optimizer core (``core/zo.py``), the adaptive optimizers
(``core/zo_adaptive.py``), the trainer, and the launch/cost tooling all
consume ZO gradients through this package's API:

    cfg  = estimators.EstimatorConfig(name="one_sided", q=16, ...)
    step, init_state = estimators.make_step(loss_fn, spec, cfg)
    params, state, metrics = step(params, state, batch, step_idx, seed)

Estimators return :class:`DirectionSet`s — (seed, coefficient) pairs
whose perturbations regenerate from seeds and are never materialized —
so optimizer memory stays params + O(q) scalars under every estimator.

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp

from repro.core import rng, zo
from repro.estimators import costs
from repro.estimators.averaged import AveragedSPSA
from repro.estimators.base import (DirectionSet, Estimator, EstimatorConfig,
                                   direction_seeds)
from repro.estimators.importance import ImportanceSelect
from repro.estimators.one_sided import OneSidedBatched
from repro.estimators.two_point import TwoPointSPSA

REGISTRY = {
    "two_point": TwoPointSPSA,
    "one_sided": OneSidedBatched,
    "averaged": AveragedSPSA,
    "importance": ImportanceSelect,
}
ESTIMATORS = tuple(REGISTRY)

__all__ = ["DirectionSet", "Estimator", "EstimatorConfig", "ESTIMATORS",
           "REGISTRY", "AveragedSPSA", "ImportanceSelect", "OneSidedBatched",
           "TwoPointSPSA", "build_estimator", "costs", "direction_seeds",
           "from_zo", "make_step"]


def build_estimator(spec: zo.ZOSpec, cfg: EstimatorConfig,
                    select_fn: Optional[Callable] = None) -> Estimator:
    if cfg.name not in REGISTRY:
        raise ValueError(
            f"unknown estimator {cfg.name!r}; pick from {ESTIMATORS}")
    if cfg.q < 1:
        raise ValueError(f"q must be >= 1, got {cfg.q}")
    if cfg.forward_backend not in costs.FORWARD_MODES:
        raise ValueError(
            f"unknown forward_backend {cfg.forward_backend!r}; pick from "
            f"{costs.FORWARD_MODES}")
    return REGISTRY[cfg.name](spec, cfg, select_fn=select_fn)


def from_zo(zo_cfg, name: str = "two_point", q: int = 1,
            **kw) -> EstimatorConfig:
    """Lift a legacy ``zo.ZOConfig`` into an EstimatorConfig."""
    return EstimatorConfig(
        name=name, eps=zo_cfg.eps, lr=zo_cfg.lr, q=q, n_drop=zo_cfg.n_drop,
        policy=zo_cfg.policy, fused_update=zo_cfg.fused_update,
        weight_decay=zo_cfg.weight_decay,
        forward_backend=getattr(zo_cfg, "forward_backend", "materialized"),
        paired_probes=getattr(zo_cfg, "paired_probes", True),
        **kw)


def make_step(loss_fn: Callable, spec: zo.ZOSpec, cfg: EstimatorConfig,
              lr_schedule: Optional[Callable] = None):
    """Build the jit-able estimator step and its state initializer.

    ``step(params, state, batch, step_idx, base_seed) -> (params, state,
    metrics)``.  ``state`` is the estimator's O(q)-scalar (or, for the
    importance wrapper, O(num_layers)-float) pytree; stateless estimators
    thread an empty dict.  Donate params and state at jit time.
    """
    est = build_estimator(spec, cfg)
    sched = lr_schedule or (lambda t: cfg.lr)

    def step(params, state, batch, step_idx, base_seed):
        seed = rng.fold(jnp.asarray(base_seed, jnp.uint32),
                        jnp.asarray(step_idx, jnp.uint32))
        p, dirs, metrics = est.estimate(loss_fn, params, batch, seed, state,
                                        step_idx)
        lr = sched(step_idx)
        decay = 1.0 - lr * cfg.weight_decay
        p = est.apply_update(p, dirs, lr, decay)
        new_state = est.update_state(state, dirs, metrics)
        metrics = dict(metrics)
        metrics["lr"] = lr
        # optimizer-health scalars (repro.obs.health): the direction
        # coefficients, LeZO layer coverage, and per-direction active
        # parameter counts make every step auditable / replayable from
        # the run log.  Cheap (a few reductions over (L,) masks), and
        # params themselves are untouched.
        if len(dirs):
            metrics["coeffs"] = jnp.stack(
                [jnp.asarray(c, jnp.float32) for c in dirs.coeffs])
            shapes = zo.leaf_shapes(params)
            metrics["n_active_params"] = jnp.stack(
                [zo.active_param_count(spec, shapes, m) for m in dirs.masks])
            if spec.num_layers:
                metrics["layer_sel"] = sum(
                    zo.global_layer_mask(spec, m).astype(jnp.int32)
                    for m in dirs.masks)
        return p, new_state, metrics

    return step, est.init_state
