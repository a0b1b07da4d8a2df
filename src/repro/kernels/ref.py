"""Pure-jnp oracle for the ZO parameter sweep (``ops.zo_axpy``).

``leaf_normal_nd`` generates z for a leaf in its *natural* shape: the
counter of element (l, i1, ..., ik) is its flat index within layer l and
the seed is fold(seed, l).  Both are computed from broadcasted iotas —
pure element-wise ops — so under pjit every device materializes exactly
its shard of z with no communication and no reshape/reshard.

Kernels of the ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.core import rng


def _within_layer_index(shape):
    """uint32 flat index over dims 1.. of ``shape`` (broadcast over dim 0)."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, 0, -1):
        idx = idx + lax.broadcasted_iota(jnp.uint32, shape, d) * np.uint32(stride)
        stride *= shape[d]
    return idx


def leaf_normal_nd(seed, shape):
    """z ~ N(0,1) for a (L, ...) leaf: z[l, i] = f(fold(seed, l), i)."""
    L = shape[0]
    seeds = rng.fold(jnp.asarray(seed, jnp.uint32),
                     jnp.arange(L, dtype=jnp.uint32))
    seeds = seeds.reshape((L,) + (1,) * (len(shape) - 1))
    idx = _within_layer_index(shape)
    return rng.counter_normal(seeds, idx)


def zo_axpy_nd(theta, mask, seed, scale, decay):
    """decay*theta + scale*z on rows where mask, theta elsewhere.

    theta: (L, ...); mask: (L,) bool or None (all active)."""
    z = leaf_normal_nd(seed, theta.shape)
    x = theta.astype(jnp.float32)
    y = (jnp.asarray(decay, jnp.float32) * x
         + jnp.asarray(scale, jnp.float32) * z).astype(theta.dtype)
    if mask is None:
        return y
    mshape = (theta.shape[0],) + (1,) * (theta.ndim - 1)
    return jnp.where(mask.reshape(mshape), y, theta)

