"""Fused virtual-perturbation matmul Pallas kernel.

Computes ``x @ (W + scale * z)`` where ``z`` never exists in HBM: each
(block_k, block_n) tile of W is read into VMEM, its z tile is regenerated
there from the counter RNG (``core.rng`` — identical streams to the axpy
kernels, see fused/ref.py for the contract), added at f32, rounded back to
the weight dtype (so the product matches the materialized perturbed
weights bit-for-bit at the tile level), and fed straight to the MXU.

This is what deletes MeZO's perturb and restore parameter sweeps: the
perturbed weights are a property of the *dataflow*, not of memory.  Per
step the parameters are read 2x (the two probe forwards — which a
forward does anyway) and written exactly once (the update axpy).

LeZO's layer skip is a scalar ``active`` predicate in SMEM: ``pl.when``
routes inactive layers to a plain matmul with zero RNG work, composing
the paper's layer sparsity with virtual perturbation multiplicatively.

Layout: grid = (M/bm, N/bn, K/bk) with K innermost; a VMEM f32 scratch
accumulates across K tiles and flushes on the last one.  Inputs are
zero-padded up to block multiples on the host side (padded K columns of
x are zero, so garbage z in the padded region contributes nothing;
padded M/N are sliced off the output), which keeps the kernel body
branch-free and interpret-mode exact.

``row_off``/``col_off`` shift the counter window: a shard holding cols
[c0, c0+n) of W passes ``col_off=c0`` and ``ld=N`` and computes exactly
its slice of the global z with no communication (see fused/sharded.py).
``trans`` reads the counters through a transpose of the stored leaf —
the tied LM head consuming ``embed/tok.T``.

Fused virtual-perturbation runtime (DESIGN.md §10).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.kernels import resolve_interpret

# (8, 128)-aligned f32 tiles; 3 buffers * 64 KiB leaves plenty of VMEM
# headroom double-buffered.
BLOCK_M = 128
BLOCK_N = 128
BLOCK_K = 128


def grid_cells(M: int, K: int, N: int, block_m: int = BLOCK_M,
               block_n: int = BLOCK_N, block_k: int = BLOCK_K) -> int:
    """Number of (i, j, k) grid cells a ``pmatmul`` of these dims runs —
    each cell loads one (bk, bn) W tile into VMEM and (when active)
    regenerates its z tile.  Shared with the oracle path so the
    structural W-traffic counters (obs.CTR_WLOAD / CTR_ZREGEN) report
    the same dataflow regardless of impl."""
    bm = min(block_m, _round_up(max(M, 1), 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 128))
    return ((_round_up(M, bm) // bm) * (_round_up(N, bn) // bn)
            * (_round_up(K, bk) // bk))


def _kernel(seed_ref, scale_ref, active_ref, offs_ref, x_ref, w_ref, o_ref,
            acc_ref, *, nk, bk, bn, ld, trans):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active_ref[0])
    def _perturbed():
        row0 = offs_ref[0] + (k * bk).astype(jnp.uint32)
        col0 = offs_ref[1] + (j * bn).astype(jnp.uint32)
        ri = row0 + lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
        ci = col0 + lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
        idx = (ci * jnp.uint32(ld) + ri) if trans \
            else (ri * jnp.uint32(ld) + ci)
        z = rng.counter_normal(seed_ref[0], idx)
        w = w_ref[...]
        weff = (w.astype(jnp.float32) + scale_ref[0] * z).astype(w.dtype)
        acc_ref[...] += jnp.dot(x_ref[...], weff,
                                preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(active_ref[0]))
    def _plain():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


@functools.partial(jax.jit, static_argnames=("trans", "ld", "block_m",
                                             "block_n", "block_k",
                                             "interpret"))
def pmatmul(x, w, seed, scale, active=None, *, trans=False, ld=None,
            row_off=0, col_off=0, block_m=BLOCK_M, block_n=BLOCK_N,
            block_k=BLOCK_K, interpret=None):
    """``x @ (w + scale*z)`` without materializing the perturbed weights.

    x: (..., K); w: (K, N); seed uint32 scalar (pre-folded per leaf and
    layer, fused/ref.layer_seed); scale f32 scalar (sign * eps); active:
    scalar bool LeZO predicate (None = always on).  ``ld``/``trans``/
    ``row_off``/``col_off`` define the counter window into the stored
    leaf (see module docstring); oracle: ``fused.ref.pmatmul``.
    ``interpret=None`` follows the platform (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    ld = (w.shape[0] if trans else N) if ld is None else ld

    bm = min(block_m, _round_up(max(M, 1), 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    x2 = jnp.pad(x2, [(0, Mp - M), (0, Kp - K)])
    wp = jnp.pad(w, [(0, Kp - K), (0, Np - N)])
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    active = jnp.bool_(True) if active is None else active
    offs = jnp.stack([jnp.asarray(row_off, jnp.uint32),
                      jnp.asarray(col_off, jnp.uint32)])
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bk=bk, bn=bn, ld=ld, trans=trans),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seed   (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scale  (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # active (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # offs   (2,)
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(
        jnp.asarray(seed, jnp.uint32).reshape(1),
        jnp.asarray(scale, jnp.float32).reshape(1),
        jnp.asarray(active, jnp.bool_).reshape(1),
        offs,
        x2,
        wp,
    )
    return out[:M, :N].reshape(*lead, N)


def _kernel_stack(seed_ref, scale_ref, active_ref, offs_ref, x_ref, w_ref,
                  o_ref, acc_ref, *, nk, bk, bn, ld, trans, nprobes,
                  shared_seed):
    """P-probe body: one W tile serves every probe.  ``x_ref``/``o_ref``/
    ``acc_ref`` carry a leading probe axis (P, bm, ·); seed/scale/active
    are (P,) SMEM vectors.  With ``shared_seed`` (the antithetic ±εz
    pair) the z tile is regenerated ONCE and reused for both signs —
    the W tile is loaded once either way.  Inactive probes fold the
    LeZO predicate into a zero scale: ``(w + 0*z)`` rounds back to ``w``
    exactly (z is finite), so a skipped layer's contribution is
    bit-identical to the plain matmul."""
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    any_active = active_ref[0]
    for p in range(1, nprobes):
        any_active = jnp.logical_or(any_active, active_ref[p])

    @pl.when(any_active)
    def _perturbed():
        row0 = offs_ref[0] + (k * bk).astype(jnp.uint32)
        col0 = offs_ref[1] + (j * bn).astype(jnp.uint32)
        ri = row0 + lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
        ci = col0 + lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
        idx = (ci * jnp.uint32(ld) + ri) if trans \
            else (ri * jnp.uint32(ld) + ci)
        w = w_ref[...]
        wf = w.astype(jnp.float32)
        z = rng.counter_normal(seed_ref[0], idx) if shared_seed else None
        for p in range(nprobes):
            zp = z if shared_seed else rng.counter_normal(seed_ref[p], idx)
            sp = jnp.where(active_ref[p], scale_ref[p],
                           jnp.zeros((), jnp.float32))
            weff = (wf + sp * zp).astype(w.dtype)
            acc_ref[p] += jnp.dot(x_ref[p], weff,
                                  preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(any_active))
    def _plain():
        w = w_ref[...]
        for p in range(nprobes):
            acc_ref[p] += jnp.dot(x_ref[p], w,
                                  preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trans", "ld", "block_m",
                                             "block_n", "block_k",
                                             "interpret", "shared_seed"))
def pmatmul_stack(x, w, seeds, scales, active=None, *, trans=False, ld=None,
                  row_off=0, col_off=0, block_m=BLOCK_M, block_n=BLOCK_N,
                  block_k=BLOCK_K, interpret=None, shared_seed=False):
    """P stacked probes of ``x_p @ (w + scales[p] * z(seeds[p]))`` in one
    kernel pass — each (bk, bn) tile of W enters VMEM once for all P
    probes instead of once per probe.

    x: (P, ..., K); w: (K, N); seeds/scales: (P,) uint32 / f32; active:
    (P,) bool per-probe LeZO predicate (None = all on).  Returns
    (P, ..., N).  ``shared_seed=True`` asserts every probe draws the
    same z (two_point's ±εz pair: seeds[p] must all equal seeds[0]) and
    regenerates each z tile once.  Counter-window args as in
    :func:`pmatmul`; oracle: ``fused.ref.pmatmul_stack``.
    """
    interpret = resolve_interpret(interpret)
    P = x.shape[0]
    lead = x.shape[1:-1]
    K = x.shape[-1]
    N = w.shape[1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(P, M, K)
    ld = (w.shape[0] if trans else N) if ld is None else ld

    bm = min(block_m, _round_up(max(M, 1), 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    x2 = jnp.pad(x2, [(0, 0), (0, Mp - M), (0, Kp - K)])
    wp = jnp.pad(w, [(0, Kp - K), (0, Np - N)])
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    active = jnp.ones((P,), jnp.bool_) if active is None else active
    offs = jnp.stack([jnp.asarray(row_off, jnp.uint32),
                      jnp.asarray(col_off, jnp.uint32)])
    out = pl.pallas_call(
        functools.partial(_kernel_stack, nk=nk, bk=bk, bn=bn, ld=ld,
                          trans=trans, nprobes=P, shared_seed=shared_seed),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seeds  (P,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scales (P,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # active (P,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # offs   (2,)
            pl.BlockSpec((P, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((P, bm, bn), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, bm, bn), jnp.float32)],
        interpret=interpret,
    )(
        jnp.asarray(seeds, jnp.uint32).reshape(P),
        jnp.asarray(scales, jnp.float32).reshape(P),
        jnp.asarray(active, jnp.bool_).reshape(P),
        offs,
        x2,
        wp,
    )
    return out[:, :M, :N].reshape(P, *lead, N)
