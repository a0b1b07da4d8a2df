"""Paper-faithful antithetic SPSA pair (MeZO/LeZO Algorithm 1).

Extracted verbatim from the pre-refactor ``core/zo.py::make_zo_step``:
the op sequence (perturb +eps, loss, perturb -2eps, loss, fused
restore+update with scale ``eps - lr*g``) is unchanged, so the lowered
XLA graph — and therefore every bit of the result — is identical to the
seed implementation (asserted in tests/test_estimators.py).

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.estimators.base import DirectionSet, Estimator
from repro.obs import trace as obs


class TwoPointSPSA(Estimator):
    name = "two_point"

    def estimate(self, loss_fn, params, batch, seed, state, step=None):
        cfg = self.cfg
        masks, idxs, n_active = self.select(seed, state, step)
        if self.virtual and cfg.paired_probes:
            # ONE paired forward for the ±εz pair: each W tile loads and
            # each z tile regenerates once for both signs — the step is
            # 1 paired forward + the single update axpy (DESIGN.md §10)
            with obs.stage(obs.FWD_PAIR) as sp:
                losses = sp.fence(self._vloss_pair(loss_fn, params, batch,
                                                   seed, cfg.eps, masks))
            l_plus, l_minus = losses[0], losses[1]
            p, restore = params, 0.0
        elif self.virtual:
            # fused forward: same z, same floats, zero parameter writes —
            # the step collapses to 2 forwards + the single update axpy
            with obs.stage(obs.FWD_PLUS) as sp:
                l_plus = sp.fence(self._vloss(loss_fn, params, batch, seed,
                                              cfg.eps, masks))
            with obs.stage(obs.FWD_MINUS) as sp:
                l_minus = sp.fence(self._vloss(loss_fn, params, batch, seed,
                                               -cfg.eps, masks))
            p, restore = params, 0.0
        else:
            with obs.stage(obs.PERTURB) as sp:
                p = sp.fence(self._ax(params, cfg.eps, seed, idxs))
            with obs.stage(obs.FWD_PLUS) as sp:
                l_plus = sp.fence(loss_fn(p, batch))
            with obs.stage(obs.PERTURB) as sp:
                p = sp.fence(self._ax(p, -2.0 * cfg.eps, seed, idxs))
            with obs.stage(obs.FWD_MINUS) as sp:
                l_minus = sp.fence(loss_fn(p, batch))
            restore = cfg.eps
        obs.get_tracer().count(obs.CTR_PROBES, 2)
        g = (l_plus - l_minus) / (2.0 * cfg.eps)
        dirs = DirectionSet(seeds=(jnp.asarray(seed, jnp.uint32),),
                            coeffs=(g,), restore=(restore,),
                            masks=(masks,), idxs=(idxs,))
        metrics = {
            "loss": 0.5 * (l_plus + l_minus),
            "projected_grad": g,
            "probe_grads": jnp.stack([jnp.asarray(g, jnp.float32)]),
            "eps": jnp.float32(cfg.eps),
            "active_layers": jnp.asarray(n_active, jnp.int32),
        }
        return p, dirs, metrics
