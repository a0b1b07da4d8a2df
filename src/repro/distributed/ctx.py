"""Mesh context for in-model sharding constraints.

Model code is mesh-agnostic; the launcher registers the active mesh here
and layers call :func:`constrain` on large intermediates where XLA's SPMD
propagation needs a nudge (the MoE dispatch buffers are the canonical
case: without a constraint the partitioner all-gathers the scatter
operand globally — 80 GiB per step on granite-moe).

``constrain`` is a no-op when no mesh is registered (CPU tests,
single-device training), and silently drops axes that don't divide, so
the same model code serves every cell of the grid.

Distributed topology context (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]):
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def _batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x, *axes):
    """axes: one of None | 'model' | 'batch' per dim of x."""
    if _MESH is None:
        return x
    mesh = _MESH
    spec = []
    for d, a in enumerate(axes):
        if a == "batch":
            ba = _batch_axes(mesh)
            n = 1
            for ax in ba:
                n *= mesh.shape[ax]
            spec.append(ba if (ba and x.shape[d] % n == 0 and x.shape[d] >= n)
                        else None)
        elif a == "model":
            n = mesh.shape.get("model", 1)
            spec.append("model" if (x.shape[d] % n == 0 and x.shape[d] >= n)
                        else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def per_batch_shard(fn, x, *rest):
    """``fn(x, *rest)`` run on each shard of x's leading (batch) dim
    alone: a ``shard_map`` that is manual over the mesh's batch axes
    only (the other axes stay with the partitioner), ``rest`` whole on
    every shard, and every output with a leading batch dim.  Row-local
    work (the MoE dispatch's sort, gather and scatter) then never
    crosses a data shard.  Without a registered mesh, or where the
    batch does not divide over its axes, ``fn`` sees the whole batch."""
    if _MESH is None:
        return fn(x, *rest)
    ba = _batch_axes(_MESH)
    n = 1
    for ax in ba:
        n *= _MESH.shape[ax]
    if n == 1 or x.shape[0] % n:
        return fn(x, *rest)
    return jax.shard_map(fn, mesh=_MESH, in_specs=(P(ba),) + (P(),) * len(rest),
                         out_specs=P(ba), axis_names=set(ba),
                         check_vma=False)(x, *rest)

