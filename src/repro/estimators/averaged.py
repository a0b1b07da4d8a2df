"""q independent antithetic SPSA pairs, averaged for variance reduction.

    ghat = (1/q) * sum_i g_i * z_i,   g_i = (L(+eps z_i) - L(-eps z_i)) / 2eps

Each probe perturbs, evaluates the pair, and restores before the next
direction, so a single parameter buffer is reused throughout; the update
then replays the q directions as q fused axpy passes, regenerating each
z_i from its seed (the ``zo_adaptive`` trick) — state stays q scalars.

At q=1 this is exactly two-point SPSA with an unfused restore, and
matches :class:`TwoPointSPSA` to float rounding (asserted in
tests/test_estimators.py).

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.estimators.base import DirectionSet, Estimator, direction_seeds


class AveragedSPSA(Estimator):
    name = "averaged"

    def estimate(self, loss_fn, params, batch, seed, state, step=None):
        cfg = self.cfg
        q = cfg.q
        seeds = direction_seeds(seed, q)
        p = params
        coeffs, masks, idxs, gs = [], [], [], []
        loss_acc = g_acc = 0.0
        n_active = None
        for s in seeds:
            m, ix, na = self.select(s, state, step)
            n_active = na if n_active is None else n_active
            if self.virtual and cfg.paired_probes:
                # the ±εz pair rides one paired fused forward — W tiles
                # and z tiles each touched once per pair (DESIGN.md §10)
                ls = self._vloss_pair(loss_fn, p, batch, s, cfg.eps, m)
                l_plus, l_minus = ls[0], ls[1]
            elif self.virtual:
                # probe pair through the fused forward: no perturb, no
                # restore-before-next-probe — params never move here
                l_plus = self._vloss(loss_fn, p, batch, s, cfg.eps, m)
                l_minus = self._vloss(loss_fn, p, batch, s, -cfg.eps, m)
            else:
                p = self._ax(p, cfg.eps, s, ix)
                l_plus = loss_fn(p, batch)
                p = self._ax(p, -2.0 * cfg.eps, s, ix)
                l_minus = loss_fn(p, batch)
                p = self._ax(p, cfg.eps, s, ix)  # restore before next
            g = (l_plus - l_minus) / (2.0 * cfg.eps)
            coeffs.append(g / q)
            gs.append(jnp.asarray(g, jnp.float32))
            masks.append(m)
            idxs.append(ix)
            loss_acc = loss_acc + 0.5 * (l_plus + l_minus)
            g_acc = g_acc + g
        dirs = DirectionSet(seeds=seeds, coeffs=tuple(coeffs),
                            restore=(0.0,) * q, masks=tuple(masks),
                            idxs=tuple(idxs))
        metrics = {
            "loss": loss_acc / q,
            "projected_grad": g_acc / q,
            "probe_grads": jnp.stack(gs),               # per-direction g_i
            "eps": jnp.float32(cfg.eps),
            "active_layers": jnp.asarray(n_active, jnp.int32),
        }
        return p, dirs, metrics
