"""Paper Fig. 2: fraction of ZO step time in forward vs perturb vs update.

The paper measures >50% of MeZO step time in perturbation+updating on
OPT-13B / SST-2 (short sequences).  We time the three stages of our MeZO
step separately (each jit'd standalone) at a params-per-token ratio
mirroring that regime, and report the perturb+update share.

Under ``forward_backend="virtual"`` (repro.fused, DESIGN.md §10) the
perturb sweeps disappear entirely — the probes run against in-kernel-
regenerated weights — so the step is 2 virtual forwards + 1 update sweep
and the perturb+update share collapses to the lone update pass; the
second half of the rows measures exactly that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import bench_model, emit, make_batch, timeit
from repro import fused
from repro.core import rng as zrng
from repro.core import zo
from repro.models import lm


def run():
    cfg, seq = bench_model()
    batch = make_batch(cfg, 16, seq)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    masks = {g: jnp.ones((l,), bool) for g, (_, l) in spec.slices.items()}
    idxs = {g: jnp.arange(l, dtype=jnp.int32)
            for g, (_, l) in spec.slices.items()}

    fwd = jax.jit(lambda p, b: lm.lm_loss(cfg, p, b))
    perturb = jax.jit(functools.partial(
        zo.tree_axpy, spec=spec, seed=jnp.uint32(1), scale=1e-3, idxs=idxs))
    update = jax.jit(functools.partial(
        zo.tree_axpy, spec=spec, seed=jnp.uint32(1), scale=-1e-6,
        idxs=idxs))

    t_fwd = timeit(fwd, params, batch)
    t_pert = timeit(perturb, params)
    t_upd = timeit(update, params)
    # one MeZO step = 2 forwards + 3 perturbs (+eps, -2eps, restore) + 1 update
    total = 2 * t_fwd + 3 * t_pert + t_upd
    share = (3 * t_pert + t_upd) / total
    rows = [
        ("stage_forward_x2", 2 * t_fwd * 1e6, f"{2 * t_fwd / total:.1%}"),
        ("stage_perturb_x3", 3 * t_pert * 1e6, f"{3 * t_pert / total:.1%}"),
        ("stage_update_x1", t_upd * 1e6, f"{t_upd / total:.1%}"),
        ("perturb_update_share", (3 * t_pert + t_upd) * 1e6,
         f"{share:.1%} (paper: >50% on OPT-13B/SST-2)"),
    ]

    # --- virtual backend: the perturb sweeps are gone by construction ---
    ctx = fused.make_ctx(jnp.uint32(1), 1e-3, masks, "virtual_ref")
    vfwd = jax.jit(lambda p, b: lm.lm_loss(cfg, p, b, perturb=ctx))
    t_vfwd = timeit(vfwd, params, batch)
    vtotal = 2 * t_vfwd + t_upd          # 2 virtual forwards + 1 update
    vshare = t_upd / vtotal
    rows += [
        ("virtual_forward_x2", 2 * t_vfwd * 1e6,
         f"{2 * t_vfwd / vtotal:.1%} (z regenerated in the forward)"),
        ("virtual_update_x1", t_upd * 1e6, f"{vshare:.1%}"),
        ("virtual_perturb_update_share", t_upd * 1e6,
         f"{vshare:.1%} (vs {share:.1%} materialized; perturb share = 0)"),
        ("virtual_step_speedup", 0.0, f"{total / vtotal:.2f}x"),
    ]
    return emit(rows)


if __name__ == "__main__":
    run()
