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
_IN_SHARD = False       # tracing the body of per_batch_shard's shard_map


def set_mesh(mesh: Optional[Mesh]):
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


def _batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def device_local() -> bool:
    """True where the code being traced runs on its device's own data
    alone, with nothing left to the partitioner: no mesh registered, a
    one-device mesh, or the body of :func:`per_batch_shard` on a mesh
    whose axes beside the batch axes all have size 1.  Elsewhere the
    partitioner may split a weight (the experts' d_ff over 'model'), and
    it cannot partition a Pallas call."""
    if _MESH is None or _MESH.size == 1:
        return True
    return _IN_SHARD and all(n == 1 for a, n in _MESH.shape.items()
                             if a not in _batch_axes(_MESH))


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
    and its axes of size 1 (the other axes stay with the partitioner),
    ``rest`` whole on every shard, and every output with a leading batch
    dim.  Row-local work (the MoE dispatch's sort, gather and scatter)
    then never crosses a data shard.  Without a registered mesh, or
    where the batch does not divide over its axes, ``fn`` sees the whole
    batch."""
    if _MESH is None:
        return fn(x, *rest)
    ba = _batch_axes(_MESH)
    n = 1
    for ax in ba:
        n *= _MESH.shape[ax]
    if n == 1 or x.shape[0] % n:
        return fn(x, *rest)

    def body(*args):
        global _IN_SHARD
        was, _IN_SHARD = _IN_SHARD, True
        try:
            return fn(*args)
        finally:
            _IN_SHARD = was

    manual = set(ba) | {a for a, s in _MESH.shape.items() if s == 1}
    return jax.shard_map(body, mesh=_MESH,
                         in_specs=(P(ba),) + (P(),) * len(rest),
                         out_specs=P(ba), axis_names=manual,
                         check_vma=False)(x, *rest)
