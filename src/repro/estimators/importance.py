"""Importance-weighted layer selection (Sparse-MeZO / LISA direction).

Replaces LeZO's uniform layer drop with a smoothed per-layer importance
score: every step, each *active* layer's score takes an EMA step toward
the magnitude of that step's projected gradient (the only attribution a
ZO step yields without extra forwards — a layer that was active while
|g| was large is credited).  Selection is Gumbel top-k by score within
each group under the same per-group quotas as
``stratified_select``, with slots of the same static shapes.

State is ``num_layers`` floats — for OPT-13B that is 40 floats next to
13B parameters, preserving the zero-extra-memory story.

This is a *wrapper*: it drives any inner estimator (``cfg.inner``,
default two_point) by injecting its weighted policy as the inner's
``select_fn``; probing, update application, and cost counts are the
inner estimator's own.

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import zo
from repro.estimators.base import DirectionSet, Estimator


class ImportanceSelect(Estimator):
    name = "importance"

    def __init__(self, spec, cfg, select_fn=None):
        super().__init__(spec, cfg, select_fn=select_fn)
        from repro import estimators as _reg  # registry; safe post-import
        inner_cls = _reg.REGISTRY[cfg.inner]
        if inner_cls is ImportanceSelect:
            raise ValueError("importance cannot wrap itself")
        self.inner = inner_cls(spec, cfg,
                               select_fn=select_fn or self._weighted_select)

    # -------------------------------------------------------- selection
    def _weighted_select(self, seed, state, step):
        return zo.stratified_select_weighted(self.spec, seed,
                                             self.cfg.n_drop, state["imp"],
                                             step=step)

    def select(self, seed, state, step=None):
        return self.inner.select(seed, state, step)

    # ------------------------------------------------------------ state
    def init_state(self):
        st = dict(self.inner.init_state())
        st["imp"] = jnp.ones((self.spec.num_layers,), jnp.float32)
        return st

    def update_state(self, state, dirs: DirectionSet, metrics):
        st = dict(self.inner.update_state(state, dirs, metrics))
        imp = state["imp"]
        q = len(dirs)
        mu = self.cfg.importance_decay
        for i in range(q):
            gmask = self._global_mask(dirs.masks[i])
            # coeffs carry the 1/q averaging weight; undo it so the score
            # tracks the raw per-direction |projected grad|.
            w = jnp.abs(jnp.asarray(dirs.coeffs[i], jnp.float32)) * q
            imp = jnp.where(gmask, mu * imp + (1.0 - mu) * w, imp)
        st["imp"] = imp
        return st

    def _global_mask(self, masks):
        return zo.global_layer_mask(self.spec, masks)

    # ------------------------------------------------- delegate probing
    def estimate(self, loss_fn, params, batch, seed, state, step=None):
        return self.inner.estimate(loss_fn, params, batch, seed, state, step)

    def restore_probe(self, params, dirs):
        return self.inner.restore_probe(params, dirs)

    def apply_update(self, params, dirs, lr, decay=1.0):
        return self.inner.apply_update(params, dirs, lr, decay)
