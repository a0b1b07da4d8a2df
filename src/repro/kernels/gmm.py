"""Grouped matmul of the MoE experts: rows sorted by group, ``lhs``'s
rows ``[o_g, o_g + group_sizes[g])`` times ``rhs[g]``, f32 out — the
contract of ``lax.ragged_dot(lhs, rhs, group_sizes,
preferred_element_type=f32)``.

Two paths, chosen from what the call observes:

  * on a TPU, the Pallas grouped matmul that ships with JAX (megablox
    ``gmm``), tiled by :func:`tiling` from the call's ``(m, k, n)``.
    The kernel walks m-tiles of ``tm`` rows; a tile that a group
    boundary cuts is computed once for each group in it, so it computes
    :func:`rows_computed` rows for ``m`` useful ones.  Its gradient is
    ``lax.ragged_dot``'s: megablox's own VJP runs ``tgmm`` and the
    transposed ``gmm`` with the forward's tiling, whose blocks, at the
    widths that tiling is chosen for, overflow the scoped VMEM.
  * ``lax.ragged_dot`` everywhere else: off a TPU (the CPU path and the
    test oracle), where no tiling fits the shapes, and where the call is
    left to the partitioner (``ctx.device_local`` is false: a mesh axis
    beside the batch axes may split the experts, or the batch does not
    divide over the data shards) — GSPMD cannot partition a Pallas call.

Kernels (DESIGN.md §2); the MoE FFN (DESIGN.md §8).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm

from repro import kernels
from repro.distributed import ctx
from repro.obs import trace as obs

F32 = jnp.float32

TM = 128                     # m-tile: the fewest rows wasted where a
                             # group boundary cuts a tile (PERF.md §6)
VMEM_BYTES = 15 * 2 ** 20    # blocks that Mosaic places in the default
                             # 16 MiB of scoped VMEM (v5e)


def _vmem_bytes(tm, tk, tn, itemsize):
    """Double-buffered lhs, rhs and f32 out blocks and the accumulator."""
    return 2 * (tm * tk + tk * tn) * itemsize + 3 * tm * tn * 4


def tiling(m: int, k: int, n: int,
           itemsize: int = 2) -> Optional[Tuple[int, int, int]]:
    """``(TM, k, n)``, or None where it does not fit: m, k and n whole
    multiples of the 128-lane tile and an expert's whole (k, n) block
    beside ``TM``-row tiles within the scoped VMEM.  The block's index
    then stays put over a group's m-tiles, so each expert's weights are
    read once; every tiling that splits k or n measured slower (the
    sweep on one v5e, PERF.md §6)."""
    if m % TM or k % 128 or n % 128:
        return None
    if _vmem_bytes(TM, k, n, itemsize) > VMEM_BYTES:
        return None
    return TM, k, n


def rows_computed(group_sizes, tm: int) -> int:
    """The rows the kernel computes: ``tm`` for every m-tile it visits,
    once for each non-empty group that has rows in the tile."""
    ends = np.cumsum(np.asarray(group_sizes, np.int64))
    starts = ends - np.asarray(group_sizes, np.int64)
    tiles = np.where(ends > starts, -(-ends // tm) - starts // tm, 0)
    return int(tiles.sum()) * tm


def _ragged_dot(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel(lhs, rhs, group_sizes, t, interpret):
    return _gmm(lhs, rhs, group_sizes, F32, t, interpret=interpret)


def _kernel_fwd(lhs, rhs, group_sizes, t, interpret):
    return (_kernel(lhs, rhs, group_sizes, t, interpret),
            (lhs, rhs, group_sizes))


def _kernel_bwd(t, interpret, res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, w: _ragged_dot(a, w, group_sizes), lhs, rhs)
    return (*vjp(g), None)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret=None):
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) int32 -> f32 (m, n).

    ``interpret`` None lets the platform choose (the kernel on a TPU,
    ``lax.ragged_dot`` elsewhere); an explicit bool runs the kernel,
    interpreted or Mosaic-compiled (tests and compile rehearsals), where
    a tiling fits.  Eager kernel calls count ``moe_gmm_rows_computed``."""
    m, k = lhs.shape
    t = tiling(m, k, rhs.shape[2], jnp.dtype(lhs.dtype).itemsize)
    if interpret is None and (kernels.default_interpret()
                              or not ctx.device_local()):
        t = None
    if t is None:
        return _ragged_dot(lhs, rhs, group_sizes)
    tr = obs.get_tracer()
    if tr.enabled and not obs.tracing():
        tr.count(obs.CTR_MOE_GMM_ROWS, rows_computed(group_sizes, t[0]))
    return _kernel(lhs, rhs, group_sizes, t,
                   kernels.resolve_interpret(interpret))
