"""Per-shard invocation of the fused kernel under a mesh.

The counter RNG makes z a pure function of the *global* (row, col) index
of each weight element, so a shard can generate exactly its slice of z
by offsetting the kernel's counter window — no communication, no
bookkeeping, the same shard-invariance ``kernels/ref.py`` gives the axpy
sweeps.  These wrappers bind that contract to the two layouts
``distributed/sharding.py`` assigns the dense projections:

  * column-parallel (wq/wk/wv/wg/wu/wi): W sharded on its last dim, x
    replicated on ``model`` — each shard passes ``col_off`` and the full
    stored row length ``ld=N``; outputs concatenate along N.
  * row-parallel (wo/wd): W sharded on its first dim, x sharded on its
    last — each shard passes ``row_off``; partial products all-reduce.

The ``virtual_ref`` forward backend needs none of this: the oracle is
plain XLA ops whose iota counters partition under pjit automatically.
These wrappers exist for running the *kernel* per shard via shard_map on
real TPUs.

Fused virtual-perturbation runtime (DESIGN.md §10).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.fused.matmul import pmatmul


def _rep(ndim: int) -> P:
    return P(*([None] * ndim))


def pmatmul_col_sharded(mesh, x, w, seed, scale, active, *, axis="model"):
    """Column-parallel fused matmul: w (K, N) sharded on N over ``axis``,
    x replicated, output sharded on its last dim."""
    N = w.shape[1]
    shard_n = N // mesh.shape[axis]

    def local(x_, w_, seed_, scale_, active_):
        c0 = (jax.lax.axis_index(axis) * shard_n).astype(jnp.uint32)
        return pmatmul(x_, w_, seed_, scale_, active_, col_off=c0, ld=N)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(_rep(x.ndim), P(None, axis), P(), P(), P()),
        out_specs=P(*([None] * (x.ndim - 1)), axis),
        check_vma=False,
    )(x, w, jnp.asarray(seed, jnp.uint32), jnp.asarray(scale, jnp.float32),
      jnp.asarray(active, jnp.bool_))


def pmatmul_row_sharded(mesh, x, w, seed, scale, active, *, axis="model"):
    """Row-parallel fused matmul: w (K, N) sharded on K over ``axis``,
    x sharded on its last dim, partial products all-reduced."""
    K, N = w.shape
    shard_k = K // mesh.shape[axis]

    def local(x_, w_, seed_, scale_, active_):
        r0 = (jax.lax.axis_index(axis) * shard_k).astype(jnp.uint32)
        part = pmatmul(x_, w_, seed_, scale_, active_, row_off=r0, ld=N)
        return jax.lax.psum(part, axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*([None] * (x.ndim - 1)), axis), P(axis, None),
                  P(), P(), P()),
        out_specs=_rep(x.ndim),
        check_vma=False,
    )(x, w, jnp.asarray(seed, jnp.uint32), jnp.asarray(scale, jnp.float32),
      jnp.asarray(active, jnp.bool_))
