"""Beyond-paper: adaptive ZO optimizers — momentum and Adam-style — with
LeZO's *zero extra memory* property preserved.

Classical ZO-momentum would store a momentum pytree (doubling memory,
defeating MeZO's point).  Observation: the SPSA update direction is
``g_t * z_t`` where ``z_t`` regenerates from (seed, t).  A K-step
momentum update is therefore a *weighted sum of regenerable directions*:

    m_t = sum_{j=0..K-1} beta^j * g_{t-j} * z_{t-j}

so it suffices to keep the last K **scalars** g_{t-j} (K*4 bytes!) and
re-apply each z from its seed — K fused axpy passes instead of one.
With LeZO sparsity each pass touches only that step's active layers, so
the extra compute is K * (1-rho) element-wise passes — and memory stays
exactly (params + a few scalars).

``zo_adam`` additionally tracks a scalar second-moment v_t of the
projected gradient (Adam's per-parameter v collapses to a scalar under
SPSA, because the per-parameter gradient estimate is g * z with z ~
N(0,1): E[(g z)^2] = g^2).  This is the ZO-AdaMM idea reduced to its
memory-free special case.

Both are property-tested for equivalence against explicit-buffer
reference implementations (tests/test_zo_adaptive.py).

ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import rng, zo


@dataclasses.dataclass(frozen=True)
class ZOMomentumConfig:
    eps: float = 1e-3
    lr: float = 1e-6
    beta: float = 0.9
    history: int = 8              # K regenerated directions
    n_drop: int = 0
    backend: str = "dense"
    adam: bool = False            # scale by 1/sqrt(v) of projected grads
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8


def make_zo_momentum_step(loss_fn: Callable, spec: zo.ZOSpec,
                          cfg: ZOMomentumConfig,
                          lr_schedule: Optional[Callable] = None):
    """State = (params, g_history (K,) f32, v_scalar) — O(K) extra bytes.

    Each step: SPSA estimate through the estimator subsystem (a two-point
    :class:`~repro.estimators.TwoPointSPSA` probe, restored immediately),
    push g_t into the ring, then apply the momentum-weighted sum of the
    last K directions, regenerating each z_{t-j} (and its layer subset)
    from (base_seed, t-j) — the same regenerate-from-seed trick the
    estimator DirectionSets are built on.
    """
    from repro import estimators  # local import: estimators builds on zo

    sched = lr_schedule or (lambda t: cfg.lr)
    K = cfg.history
    est = estimators.build_estimator(
        spec, estimators.EstimatorConfig(
            name="two_point", eps=cfg.eps, lr=cfg.lr, n_drop=cfg.n_drop,
            policy="stratified", backend=cfg.backend, fused_update=False))

    def init_state():
        return {"g_hist": jnp.zeros((K,), jnp.float32),
                "v": jnp.zeros((), jnp.float32),
                "count": jnp.zeros((), jnp.int32)}

    def step(params, state, batch, step_idx, base_seed):
        seed = rng.fold(jnp.asarray(base_seed, jnp.uint32),
                        jnp.asarray(step_idx, jnp.uint32))
        ax = lambda p, s, sd, m, i: zo.tree_axpy(
            p, spec, sd, s, m, i, backend=cfg.backend)

        # SPSA probe + immediate restore (unfused: momentum owns the update)
        p, dirs, em = est.estimate(loss_fn, params, batch, seed, state)
        p = est.restore_probe(p, dirs)
        g = dirs.coeffs[0]

        g_hist = jnp.roll(state["g_hist"], 1).at[0].set(g)
        count = state["count"] + 1
        v = cfg.adam_beta2 * state["v"] + (1 - cfg.adam_beta2) * g * g
        lr = sched(step_idx)
        if cfg.adam:
            vhat = v / (1 - cfg.adam_beta2 ** count.astype(jnp.float32))
            lr = lr / (jnp.sqrt(vhat) + cfg.adam_eps)

        # momentum: re-apply the last K directions with beta^j weights.
        # j runs over history; steps before 0 contribute g=0 (ring init).
        def apply_j(j, p):
            t_j = step_idx - j
            seed_j = rng.fold(jnp.asarray(base_seed, jnp.uint32),
                              jnp.asarray(t_j, jnp.uint32))
            masks_j, idxs_j, _ = est.select(seed_j, state)
            scale = -lr * (cfg.beta ** j.astype(jnp.float32)) * g_hist[j]
            valid = (t_j >= 0).astype(jnp.float32)
            return ax(p, scale * valid, seed_j, masks_j, idxs_j)

        p = jax.lax.fori_loop(0, K, apply_j, p)
        new_state = {"g_hist": g_hist, "v": v, "count": count}
        metrics = {"loss": em["loss"], "projected_grad": g, "lr": lr}
        return p, new_state, metrics

    return step, init_state
