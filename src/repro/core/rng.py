"""Counter-based RNG for zeroth-order perturbations.

MeZO/LeZO's memory trick is that the perturbation vector ``z`` is never
stored: it is regenerated from a seed for the +eps pass, the -2*eps pass,
the restore pass and the update pass.  PyTorch does this with a sequential
generator (``torch.manual_seed`` + ordered draws), which bakes in an
iteration *order* over modules and cannot be sharded without bookkeeping.

We instead make ``z`` a pure function of ``(seed, element index)``::

    z[l, i] = normal(mix(seed, leaf_uid, l), i)

so that every device holding any shard of a parameter computes exactly the
bits that correspond to its slice, with zero communication and zero state.
The same functions run inside Pallas kernel bodies (element-wise uint32 ops
only) and in the pure-jnp oracle, so kernel vs. reference comparisons are
bit-exact.

The generator is a 3-round murmur3-style finalizer over a distinct counter
per element ("lowbias32"); two decorrelated streams feed a Box-Muller
transform.  Statistical quality is validated in tests/test_rng.py (moments,
cross-correlation, uniqueness across layers/leaves).

ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

# Distinct odd constants (murmur3/xxhash lineage).
_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_S2 = np.uint32(0x85EBCA6B)
_TWO_PI = np.float32(2.0 * np.pi)


def mix32(x):
    """Murmur3-style avalanche over uint32 (works on scalars and arrays)."""
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def fold(seed, data):
    """Derive a new uint32 seed from (seed, data) — order matters."""
    seed = jnp.asarray(seed, jnp.uint32)
    data = jnp.asarray(data, jnp.uint32)
    return mix32(seed * _GOLDEN + data + _M2)


def fold_py(seed: int, data: int) -> int:
    """Python-int version of :func:`fold` for trace-time seed derivation."""
    m = 0xFFFFFFFF
    x = (seed * 0x9E3779B9 + data + 0x846CA68B) & m
    x ^= x >> 16
    x = (x * 0x7FEB352D) & m
    x ^= x >> 15
    x = (x * 0x846CA68B) & m
    x ^= x >> 16
    return x


def _uniform01(bits):
    """uint32 -> float32 uniform in (0, 1]; never 0 so log() is safe."""
    # Take the top 24 bits -> [0, 2^24), scale to (0,1].  The value fits
    # int32 exactly, and Mosaic casts int32 (not uint32) to float32.
    top = (bits >> np.uint32(8)).astype(jnp.int32)
    return (top.astype(jnp.float32) + 1.0) * np.float32(1.0 / 16777216.0)


def counter_normal(seed, counters):
    """Standard normals, one per counter.

    ``seed`` is a uint32 scalar (may be traced); ``counters`` any uint32
    array of element indices.  Element-wise ops only — safe inside Pallas.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    c = jnp.asarray(counters, jnp.uint32)
    h1 = mix32(c * _GOLDEN + seed)
    h2 = mix32((c + _S2) * _GOLDEN + (seed ^ _S2))
    u1 = _uniform01(h1)
    u2 = _uniform01(h2)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(_TWO_PI * u2)


def leaf_uid(path: str) -> int:
    """Stable uint32 id for a parameter leaf from its tree path string."""
    h = 2166136261  # FNV-1a
    for ch in path.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h
