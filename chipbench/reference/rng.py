"""The counter RNG of the ZO step, written from its definition.

A ZO step never stores its direction z: element i of layer l of a leaf
at tree path ``path`` is, for the step seed s,

    z = N(fold(fold(s, uid(path)), l), i)

where ``uid`` is 32-bit FNV-1a over the path's bytes, ``fold(a, b)`` is
a murmur3-style finalizer of ``a * 0x9E3779B9 + b + 0x846CA68B``, ``i``
is the element's flat index within its layer (a leaf that is not stacked
over layers is one layer, l = 0), and N draws two decorrelated uniforms
from the counter and returns the Box-Muller cosine normal.  The
benchmark's weights come from the same generator under a seed of their
own (``weight_seed``), so they are made on the device, elementwise, in
one pass.

Nothing here imports the program: this is the reference's own copy of
the semantics, and the benchmark holds the program to it.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

GOLDEN = np.uint32(0x9E3779B9)
M1 = np.uint32(0x7FEB352D)
M2 = np.uint32(0x846CA68B)
S2 = np.uint32(0x85EBCA6B)
TWO_PI = np.float32(2.0 * np.pi)
MASK32 = 0xFFFFFFFF


def mix32(x):
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * M1
    x = x ^ (x >> 15)
    x = x * M2
    return x ^ (x >> 16)


def fold(seed, data):
    seed = jnp.asarray(seed, jnp.uint32)
    data = jnp.asarray(data, jnp.uint32)
    return mix32(seed * GOLDEN + data + M2)


def fold_py(seed: int, data: int) -> int:
    x = (seed * 0x9E3779B9 + data + 0x846CA68B) & MASK32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & MASK32
    x ^= x >> 15
    x = (x * 0x846CA68B) & MASK32
    return x ^ (x >> 16)


def uid(path: str) -> int:
    h = 2166136261
    for ch in path.encode():
        h = ((h ^ ch) * 16777619) & MASK32
    return h


def _unit(bits):
    """Top 24 bits -> (0, 1], never 0."""
    top = (bits >> np.uint32(8)).astype(jnp.int32)
    return (top.astype(jnp.float32) + 1.0) * np.float32(1.0 / 16777216.0)


def normal(seed, counters):
    c = jnp.asarray(counters, jnp.uint32)
    seed = jnp.asarray(seed, jnp.uint32)
    u1 = _unit(mix32(c * GOLDEN + seed))
    u2 = _unit(mix32((c + S2) * GOLDEN + (seed ^ S2)))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(TWO_PI * u2)


def layer_index(shape):
    """uint32 flat index of each element within its layer (dims 1..)."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, 0, -1):
        idx = idx + lax.broadcasted_iota(jnp.uint32, shape, d) * np.uint32(stride)
        stride *= shape[d]
    return idx


def layer_normals(leaf_seed, layer, shape):
    """z of one layer (``shape`` without the layer axis)."""
    full = (1,) + tuple(shape)
    return normal(fold(leaf_seed, layer), layer_index(full))[0]


def weight_seed(seed: int) -> int:
    """The 32-bit stream the weights of a run come from; any whole
    number, of any size, names one."""
    s = int(seed)
    out = fold_py(0x3E1647, s & MASK32)
    while s > MASK32:
        s >>= 32
        out = fold_py(out, s & MASK32)
    return out
