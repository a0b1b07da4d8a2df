"""Backend dispatch for the fused ZO axpy.

Four implementations of the same contract (oracle: ``ref.zo_axpy_nd``):

  * ``dense``  — masked element-wise pass in the leaf's natural shape.
                 Computes z for dropped layers too (what a naive port
                 does), but XLA fuses RNG+axpy into one HBM-speed loop
                 and it shards with zero communication.  MeZO (n_drop=0)
                 uses this: every layer is active anyway.
  * ``scan``   — with ``active_idx`` (stratified / weighted policies):
                 a fori_loop over the enabled active slots updates each
                 listed row of the donated leaf in place; dropped rows
                 and disabled slots are never read or written and no
                 stacked buffer is made.  Without
                 it (uniform policy, dynamic per-group count): lax.scan
                 over the layer axis + lax.cond per layer, dropped layers
                 skip RNG + axpy compute into a fresh stacked buffer.
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
from repro.obs import trace as obs

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


def _rows_axpy(theta, active_idx, n, seed, scale, decay):
    """Update only the rows listed in the first ``n`` slots of
    active_idx, in place: a fori_loop over those slots carries the leaf,
    slicing row ``active_idx[j]`` out and writing it back.  Dropped rows
    are neither read nor written, no stacked buffer is made, and the
    counters are built inside the body, where they fuse into the RNG.
    A traced ``n`` is the loop's trip count: disabled slots cost
    nothing."""
    row_shape = theta.shape[1:]
    scale = jnp.asarray(scale, jnp.float32)
    decay = jnp.asarray(decay, jnp.float32)

    def body(j, theta):
        l = active_idx[j]
        row = lax.dynamic_index_in_dim(theta, l, keepdims=False)
        idx = kref._within_layer_index((1,) + row_shape)[0]
        z = rng.counter_normal(rng.fold(seed, l), idx)
        row = (decay * row.astype(jnp.float32) + scale * z).astype(theta.dtype)
        return lax.dynamic_update_index_in_dim(theta, row, l, 0)

    return lax.fori_loop(0, n, body, theta)


def _gather_axpy(theta, active_idx, seed, scale, decay):
    """Perturb exactly the rows listed in active_idx (static length k);
    a disabled slot's index L gathers a clamped row whose write-back
    the scatter drops."""
    rows = theta[active_idx]
    rows = kref.zo_axpy_nd(rows, None, seed, scale, decay,
                           layer_ids=active_idx.astype(jnp.uint32))
    return theta.at[active_idx].set(rows)


def zo_axpy(theta, *, path, seed, scale, decay=1.0, mask=None,
            active_idx=None, backend="dense"):
    """Apply ``decay*theta + scale*z`` to a parameter leaf.

    theta is stacked over layers on axis 0 iff ``mask``/``active_idx`` is
    given.  ``path`` (tree-path string) keys the leaf's z stream.
    ``active_idx``: static-size index vector of active layers, or a
    (vector, enabled count) pair whose disabled slots hold L (as
    ``core.zo.stratified_select`` gives for a group whose drop count
    varies by step) — required for the gather backend, the in-place rows
    for scan, ignored otherwise.  Each stacked leaf counts the rows its
    sweep reads and writes (``axpy_rows_swept``, eager runs): the
    enabled slots where only the active rows are touched, L where the
    whole stack is.
    """
    leaf_seed = rng.fold(jnp.asarray(seed, jnp.uint32),
                         jnp.uint32(rng.leaf_uid(path)))
    if mask is None and active_idx is None:
        # whole leaf always active: single pseudo-layer, natural shape
        return kref.zo_axpy_nd(theta[None], None, leaf_seed, scale,
                               decay)[0]
    n_on = None
    if isinstance(active_idx, tuple):
        active_idx, n_on = active_idx
    elif active_idx is not None:
        n_on = active_idx.shape[0]
    tr = obs.get_tracer()
    if tr.enabled and not obs.tracing():
        rows_only = n_on is not None and backend in ("scan", "gather")
        tr.count(obs.CTR_AXPY_ROWS, int(n_on) if rows_only
                 else theta.shape[0])
    if backend == "dense":
        return kref.zo_axpy_nd(theta, mask, leaf_seed, scale, decay)
    if backend == "scan":
        if active_idx is not None:
            return _rows_axpy(theta, active_idx, n_on, leaf_seed, scale,
                              decay)
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
