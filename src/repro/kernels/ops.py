"""The ZO parameter sweep: ``theta <- decay*theta + scale*z`` on one leaf.

One path (oracle: ``ref.zo_axpy_nd``).  A leaf with no layer group is
one pseudo-layer swept whole in its natural shape.  A leaf stacked over
a layer group is swept row by row: a fori_loop over the step's active
slots (every selection policy gives them, ``core.zo``) slices each
listed row out of the donated leaf, regenerates its z and writes it
back in place.  Dropped rows are neither read nor written, no stacked
buffer is made, and a traced slot count is the loop's trip count, so
disabled slots cost nothing.  That is why it sweeps in place: LeZO's
sweeps are bound by memory traffic, and every byte of a dropped row
left untouched is saved.

z is the counter RNG keyed by (seed, leaf, global layer id), so a row's
z does not depend on which other rows are active.

Kernels of the ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import rng
from repro.kernels import ref as kref
from repro.obs import trace as obs


def _rows_axpy(theta, active_idx, n, seed, scale, decay):
    """Update only the rows listed in the first ``n`` slots of
    active_idx, in place: a fori_loop over those slots carries the leaf,
    slicing row ``active_idx[j]`` out and writing it back.  The counters
    are built inside the body, where they fuse into the RNG."""
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


def zo_axpy(theta, *, path, seed, scale, decay=1.0, active_idx=None):
    """Apply ``decay*theta + scale*z`` to a parameter leaf.

    ``path`` (tree-path string) keys the leaf's z stream.  Without
    ``active_idx`` the whole leaf is swept.  With it, theta is stacked
    over layers on axis 0 and only the listed rows are swept:
    ``active_idx`` is a static-size vector of layer ids, or a (vector,
    enabled count) pair whose disabled slots come last (see
    ``core.zo._slots``).  Each stacked leaf counts the rows it sweeps
    (``axpy_rows_swept``, eager runs).
    """
    leaf_seed = rng.fold(jnp.asarray(seed, jnp.uint32),
                         jnp.uint32(rng.leaf_uid(path)))
    if active_idx is None:
        return kref.zo_axpy_nd(theta[None], None, leaf_seed, scale,
                               decay)[0]
    if isinstance(active_idx, tuple):
        active_idx, n_on = active_idx
    else:
        n_on = active_idx.shape[0]
    tr = obs.get_tracer()
    if tr.enabled and not obs.tracing():
        tr.count(obs.CTR_AXPY_ROWS, int(n_on))
    return _rows_axpy(theta, active_idx, n_on, leaf_seed, scale, decay)
