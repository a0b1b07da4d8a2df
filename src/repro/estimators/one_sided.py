"""FZOO-style batched one-sided estimator (arXiv:2506.09034).

One unperturbed baseline forward is amortized across q one-sided probes:

    g_i = (L(theta + eps * z_i) - L(theta)) / eps
    ghat = (1/q) * sum_i g_i * z_i

The q perturbed evaluations are vmapped over a perturbation-seed axis so
XLA batches them into one widened forward (weight matmuls become batched
matmuls; the counter RNG regenerates each z_i inside the vmapped region,
so no (q, params) tree outlives the fused forward).  Compute still scales
with q — see estimators/costs.py — but per-probe overhead (dispatch,
baseline loss, non-width-scaling work) is paid once.

The probe perturbation inside the vmap always uses the dense axpy path:
a widened forward wants one fused elementwise RNG+axpy that XLA batches
across the q-axis.  The configured backend (scan/gather/pallas) governs
the q sequential update sweeps that follow, where layer skipping pays.

Memory: optimizer *state* stays O(q) scalars (the DirectionSet), but the
widened forward transiently holds up to q perturbed copies of the active
parameters as its working set (fused into the batched matmuls where XLA
can).  On memory-tight models set ``q_chunk`` to bound that: probes are
vmapped ``q_chunk`` at a time and the chunks run sequentially.

Variance of the one-sided estimate is higher per probe than antithetic
two-point (the Hessian term (eps/2) z'Hz does not cancel), but averaging
q probes for one extra forward — instead of q extra forward *pairs* —
wins on compute at equal variance for q >= 2.

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import zo
from repro.estimators.base import DirectionSet, Estimator, direction_seeds
from repro.obs import trace as obs


class OneSidedBatched(Estimator):
    name = "one_sided"

    def estimate(self, loss_fn, params, batch, seed, state):
        cfg = self.cfg
        q = cfg.q
        seeds = direction_seeds(seed, q)
        sels = [self.select(s, state) for s in seeds]
        masks = tuple(s[0] for s in sels)
        idxs = tuple(s[1] for s in sels)
        n_active = sels[0][2]

        with obs.stage(obs.FWD_BASE) as sp:
            l0 = sp.fence(loss_fn(params, batch))
        seeds_arr = jnp.stack([jnp.asarray(s, jnp.uint32) for s in seeds])
        stacked_masks = ({g: jnp.stack([m[g] for m in masks])
                          for g in masks[0]} if masks[0] else {})

        def probe(seed_i, masks_i):
            if self.virtual:
                # q probes are q *seeds* of the same weights: the vmapped
                # fused forward regenerates each z_i in-kernel, so no
                # widened (q, params) perturbed copies ever exist
                return self._vloss(loss_fn, params, batch, seed_i,
                                   cfg.eps, masks_i)
            p = zo.tree_axpy(params, self.spec, seed_i, cfg.eps, masks_i,
                             None, backend="dense")
            return loss_fn(p, batch)

        chunk = cfg.q_chunk if 0 < cfg.q_chunk < q else q
        # One stage over all q probes: the vmapped region itself traces,
        # so an eager run could time no span per probe inside it.
        with obs.stage(obs.FWD_PLUS) as sp:
            parts = []
            for c0 in range(0, q, chunk):
                sub_masks = {g: m[c0:c0 + chunk]
                             for g, m in stacked_masks.items()}
                if self.virtual and cfg.paired_probes:
                    # stacked kernel pass: the chunk's probes share one
                    # sweep over W (per-probe z streams stay intact) —
                    # same floats as the vmapped path, fewer tile loads
                    parts.append(self._vloss_stack(
                        loss_fn, params, batch, seeds_arr[c0:c0 + chunk],
                        cfg.eps, sub_masks))
                else:
                    parts.append(jax.vmap(probe)(seeds_arr[c0:c0 + chunk],
                                                 sub_masks))
            losses = sp.fence(parts[0] if len(parts) == 1
                              else jnp.concatenate(parts))
        obs.get_tracer().count(obs.CTR_PROBES, q)
        g = (losses - l0) / cfg.eps                     # (q,) projections
        coeffs = tuple(g[i] / q for i in range(q))
        dirs = DirectionSet(seeds=seeds, coeffs=coeffs, restore=(0.0,) * q,
                            masks=masks, idxs=idxs)
        metrics = {
            "loss": l0,                                 # unperturbed loss
            "projected_grad": jnp.mean(g),
            "probe_grads": g.astype(jnp.float32),       # per-probe g_i
            "eps": jnp.float32(cfg.eps),
            "active_layers": jnp.asarray(n_active, jnp.int32),
        }
        return params, dirs, metrics
