"""Backend dispatch for the fused ZO axpy.

Four implementations of the same contract (oracle: ``ref.zo_axpy_nd``):

  * ``dense``  — masked element-wise pass in the leaf's natural shape.
                 Computes z for dropped layers too (what a naive port
                 does), but XLA fuses RNG+axpy into one HBM-speed loop
                 and it shards with zero communication.  MeZO (n_drop=0)
                 uses this: every layer is active anyway.
  * ``scan``   — lax.scan over the layer axis + lax.cond per layer: a
                 real runtime branch, dropped layers skip RNG + axpy
                 compute.  Paper-faithful "skip" in pure JAX.
  * ``gather`` — beyond-paper: LeZO's active set has *static* size
                 k = N - n_drop, so gather the k active rows, run the
                 dense pass on the compact (k, ...) buffer, scatter back.
                 Work is k-proportional *in the HLO itself* (visible to
                 cost_analysis, shardable on non-layer dims) at the price
                 of one extra gather+scatter stream.
  * ``pallas`` — the fused TPU kernel (``zo_axpy.zo_axpy_2d``): on-the-fly
                 RNG in VMEM, per-layer ``pl.when`` predication, buffer
                 aliasing.  Compiled by Mosaic on a TPU, run by the
                 Pallas interpreter elsewhere (the platform decides).

All backends draw identical z (same counter RNG keyed by (seed, leaf,
global layer id)) — property-tested against each other.

Kernel backends of the ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import rng
from repro.kernels import ref as kref
from repro.kernels import zo_axpy as kzo

BACKENDS = ("dense", "scan", "gather", "pallas")


def _scan_axpy(theta, mask, seed, scale, decay):
    row_shape = theta.shape[1:]
    idx = kref._within_layer_index((1,) + row_shape)[0]
    scale = jnp.asarray(scale, jnp.float32)
    decay = jnp.asarray(decay, jnp.float32)

    def active(args):
        row, l = args
        z = rng.counter_normal(rng.fold(seed, l), idx)
        return (decay * row.astype(jnp.float32) + scale * z).astype(theta.dtype)

    def body(_, inp):
        row, m, l = inp
        out = lax.cond(m, active, lambda a: a[0], (row, l))
        return None, out

    L = theta.shape[0]
    _, out = lax.scan(body, None,
                      (theta, mask, jnp.arange(L, dtype=jnp.uint32)))
    return out


def _gather_axpy(theta, active_idx, seed, scale, decay):
    """Perturb exactly the rows listed in active_idx (static length k)."""
    rows = theta[active_idx]
    rows = kref.zo_axpy_nd(rows, None, seed, scale, decay,
                           layer_ids=active_idx.astype(jnp.uint32))
    return theta.at[active_idx].set(rows)


def zo_axpy(theta, *, path, seed, scale, decay=1.0, mask=None,
            active_idx=None, backend="dense"):
    """Apply ``decay*theta + scale*z`` to a parameter leaf.

    theta is stacked over layers on axis 0 iff ``mask``/``active_idx`` is
    given.  ``path`` (tree-path string) keys the leaf's z stream.
    ``active_idx``: static-size index vector of active layers — required
    for the gather backend, ignored otherwise.
    """
    leaf_seed = rng.fold(jnp.asarray(seed, jnp.uint32),
                         jnp.uint32(rng.leaf_uid(path)))
    if mask is None and active_idx is None:
        # whole leaf always active: single pseudo-layer, natural shape
        return kref.zo_axpy_nd(theta[None], None, leaf_seed, scale,
                               decay)[0]
    if backend == "dense":
        return kref.zo_axpy_nd(theta, mask, leaf_seed, scale, decay)
    if backend == "scan":
        return _scan_axpy(theta, mask, leaf_seed, scale, decay)
    if backend == "gather":
        if active_idx is None:
            raise ValueError("gather backend needs active_idx")
        return _gather_axpy(theta, active_idx, leaf_seed, scale, decay)
    if backend == "pallas":
        theta2d = theta.reshape(theta.shape[0], -1)
        out = kzo.zo_axpy_2d(theta2d, mask, leaf_seed, scale, decay)
        return out.reshape(theta.shape)
    raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
