"""Virtual-perturbation fused forward runtime (DESIGN.md §10).

MeZO/LeZO spend >50% of step time sweeping parameters: perturb(+eps),
perturb(-2eps), restore, update.  But with a counter-based RNG, z is a
pure function of (seed, leaf, layer, element) — so the perturbed weights
``theta + s*eps*z`` never need to exist in HBM: the forward pass can
regenerate z inside its matmul tiles and compute ``x @ (W + s*eps*z)``
on the fly.  A two-point ZO step becomes exactly

    2 virtual forwards + 1 fused update axpy

with zero perturb/restore parameter writes, which composes
multiplicatively with LeZO's per-layer skip (the kernels carry the
active predicate) and with the batched estimators in ``repro.estimators``
(one_sided's q probes are q *seeds* of the same weights — no widened
parameter copies).

Pieces:
  * ``ref``      — pure-JAX oracle + the z-consistency contract with
                   ``kernels/ops.py`` (same streams as the axpy sweeps).
  * ``pmatmul``  — the Pallas TPU kernel (the Pallas interpreter runs it
                   off the TPU).
  * ``view``     — PerturbCtx / LayerPerturb lens the model forward
                   consumes (``lm.lm_loss(..., perturb=ctx)``).
  * ``sharded``  — shard_map wrappers with global counter offsets.

Select it with ``forward_backend="virtual"`` (Pallas) or
``"virtual_ref"`` (oracle, pjit-shardable) on ZOConfig / EstimatorConfig
/ TrainConfig; ``"materialized"`` is the classic perturb-restore path.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.estimators.costs import FORWARD_MODES
from repro.fused import ref
from repro.fused.matmul import pmatmul, pmatmul_stack
from repro.fused.sharded import pmatmul_col_sharded, pmatmul_row_sharded
from repro.fused.view import IMPLS, LayerPerturb, PerturbCtx, ProbePair

__all__ = ["FORWARD_MODES", "IMPLS", "LayerPerturb", "PerturbCtx",
           "ProbePair", "make_ctx", "make_pair_ctx",
           "make_stack_ctx", "pmatmul", "pmatmul_col_sharded",
           "pmatmul_row_sharded", "pmatmul_stack", "ref"]


def _impl_of(forward_backend: str) -> str:
    if forward_backend not in FORWARD_MODES[1:]:
        raise ValueError(
            f"not a virtual forward backend: {forward_backend!r}; "
            f"pick from {FORWARD_MODES[1:]}")
    return "ref" if forward_backend == "virtual_ref" else "pallas"


def make_ctx(seed, scale, masks, forward_backend: str) -> PerturbCtx:
    """Build the perturbation lens for one probe of ``forward_backend``."""
    return PerturbCtx(seed=jnp.asarray(seed, jnp.uint32),
                      scale=jnp.asarray(scale, jnp.float32),
                      masks=masks, impl=_impl_of(forward_backend))


def make_pair_ctx(seed, eps, masks, forward_backend: str) -> PerturbCtx:
    """The antithetic ±εz pair as ONE stacked ctx: probe 0 is +eps,
    probe 1 is -eps, both drawing the identical z stream (shared seed) —
    the fused forward loads every W tile and regenerates every z tile
    once for the pair.  ``lm_loss`` under this ctx returns a (2,) loss
    vector ``[l_plus, l_minus]``."""
    s = jnp.asarray(seed, jnp.uint32)
    e = jnp.asarray(eps, jnp.float32)
    sm = (None if masks is None else
          {g: jnp.broadcast_to(m, (2,) + m.shape) for g, m in masks.items()})
    return PerturbCtx(seed=jnp.stack([s, s]),
                      scale=jnp.stack([e, -e]),
                      masks=sm, impl=_impl_of(forward_backend),
                      pair=ProbePair(n=2, shared_seed=True))


def make_stack_ctx(seeds, scales, masks, forward_backend: str) -> PerturbCtx:
    """P independent probes stacked on one forward (one_sided's q
    probes): ``seeds``/``scales`` are (P,) vectors, ``masks`` maps group
    -> (P, L_g).  W tiles are loaded once for all P probes; z streams
    stay per-seed.  ``lm_loss`` returns a (P,) loss vector."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    P = seeds.shape[0]
    return PerturbCtx(seed=seeds,
                      scale=jnp.broadcast_to(
                          jnp.asarray(scales, jnp.float32), (P,)),
                      masks=masks, impl=_impl_of(forward_backend),
                      pair=ProbePair(n=P, shared_seed=False))
