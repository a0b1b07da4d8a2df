"""Fused ZO perturb/update Pallas kernel.

The MeZO/LeZO hot spot is the element-wise pass

    theta <- decay * theta + scale * z(seed, index)

executed several times per optimization step over *every* parameter.  The
paper measures this at >50% of step time on OPT-13B.  On TPU the pass is
HBM-bandwidth-bound, so the kernel's job is to touch each parameter byte
exactly twice (read + write):

  * ``z`` is generated *inside* the kernel from a counter-based RNG
    (``core.rng``) — it never exists in HBM.  (The PyTorch original
    materializes a z tensor per module: 3x the traffic.)
  * LeZO's layer skip is a ``pl.when`` predicate on a per-layer mask held
    in SMEM: dropped layers do no RNG/FLOP work and, thanks to
    input/output aliasing, no data movement either on TPU.
  * ``decay`` folds weight decay into the same pass; ``scale`` is a
    runtime scalar (SMEM) so the *restore* (+eps) and *update* (-lr*g)
    passes fuse into one call with scale = eps - lr*g.

Layout: a parameter leaf is viewed as (L, n) — L stacked layers (L=1 for
unstacked leaves) by n flattened elements — and each layer row as a
row-major (rows, lanes) matrix, lanes a multiple of 128 (n is padded up
to a multiple of 128 when it is not one).  Grid = (L, ceil(rows / br));
the layer axis is squeezed out of the block, so every VMEM block is a
(br, lanes) tile that meets the TPU's (8, 128) tiling.  ``block`` bounds
the elements per tile: 64Ki elements are 256 KiB of f32 per buffer, far
under the scoped-VMEM budget even double-buffered.

Counters restart at 0 for every (leaf, layer) and equal the element's
flat index within the layer, whatever the tiling: uniqueness across
leaves and layers comes from folding (leaf uid, layer index) into the
seed, which keeps counters within uint32 for any realistic leaf and makes
the value of z[l, i] independent of tiling and sharding.

Kernel backends of the ZO core (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.kernels import resolve_interpret

BLOCK = 65536   # elements per tile: 256 KiB of f32 in, 256 KiB out in VMEM.
_LANES = (1024, 512, 256, 128)
_SUBLANES = 32  # row-block multiple: native (8|16|32, 128) tiles of f32/bf16/int8


def _layout(n: int, block: int):
    """-> (padded n, lanes, rows, rows per block) for one layer row."""
    n_pad = -(-n // 128) * 128
    lanes = next(w for w in _LANES if n_pad % w == 0)
    rows = n_pad // lanes
    br = max(_SUBLANES, block // lanes // _SUBLANES * _SUBLANES)
    return n_pad, lanes, rows, min(br, rows)


def _kernel(mask_ref, seed_ref, scale_ref, decay_ref, theta_ref, out_ref, *,
            br, lanes):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(mask_ref[l] != 0)
    def _active():
        # Per-(leaf, layer) seed was pre-folded on the host side up to the
        # leaf uid; fold the layer index here (scalar uint32 math).
        seed_l = rng.fold(seed_ref[0], l.astype(jnp.uint32))
        row = (j * br).astype(jnp.uint32) + jax.lax.broadcasted_iota(
            jnp.uint32, (br, lanes), 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, (br, lanes), 1)
        z = rng.counter_normal(seed_l, row * jnp.uint32(lanes) + col)
        x = theta_ref[...].astype(jnp.float32)
        y = decay_ref[0] * x + scale_ref[0] * z
        out_ref[...] = y.astype(out_ref.dtype)

    @pl.when(mask_ref[l] == 0)
    def _skipped():
        # Write-through keeps interpret-mode semantics correct; on TPU the
        # buffer is aliased so this is a VMEM-local copy, no HBM traffic
        # beyond the (already scheduled) block in/out.
        out_ref[...] = theta_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def zo_axpy_2d(theta, mask, seed, scale, decay, *, block=BLOCK,
               interpret=None):
    """theta: (L, n); mask: (L,) bool; seed uint32 scalar; scale/decay f32
    scalars.  ``interpret=None`` follows the platform.

    Returns decay*theta + scale*z for rows where mask, theta elsewhere.
    """
    L, n = theta.shape
    n_pad, lanes, rows, br = _layout(n, block)
    view = theta if n_pad == n else jnp.pad(theta, [(0, 0), (0, n_pad - n)])
    view = view.reshape(L, rows, lanes)
    tile = pl.BlockSpec((None, br, lanes), lambda l, j: (l, j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, br=br, lanes=lanes),
        grid=(L, pl.cdiv(rows, br)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # mask  (L,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed  (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # decay (1,)
            tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(view.shape, theta.dtype),
        input_output_aliases={4: 0},
        interpret=resolve_interpret(interpret),
    )(
        jnp.asarray(mask, jnp.int32),
        jnp.asarray(seed, jnp.uint32).reshape(1),
        jnp.asarray(scale, jnp.float32).reshape(1),
        jnp.asarray(decay, jnp.float32).reshape(1),
        view,
    )
    out = out.reshape(L, n_pad)
    return out if n_pad == n else out[:, :n]
