"""PerturbView lens: thread virtual perturbation through a forward pass.

A :class:`PerturbCtx` is the whole perturbation — (seed, scale, LeZO
masks) plus static impl flags — created per probe inside the estimator's
trace and handed to ``models.lm.lm_loss(..., perturb=ctx)``.  The model
derives a :class:`LayerPerturb` handle per (block, layer) as its stage
scan walks the stacked parameters; the handle knows the leaf-path prefix
(static string), the layer index within the stacked axis-0 (traced) and
the layer's active predicate (traced bool), which is everything needed to
reproduce the exact per-leaf z streams of the axpy sweeps
(fused/ref.py's z-consistency contract).

``impl="pallas"`` routes matmuls through the fused kernel
(fused/matmul.py; Pallas interpret mode follows the platform);
``impl="ref"`` uses the pure-JAX oracle — same floats, ordinary XLA ops,
shards under pjit.  Vector-sized leaves (norm scale/bias) always use the
oracle: an O(D) temp is activation-sized, and a kernel launch would cost
more than the add.

Paired probes (:class:`ProbePair`): a ctx may carry P stacked probes —
per-probe (P,) seed/scale vectors riding ONE forward whose activations
fold the probe axis into the batch dim ((P·B, S, D), p-major).  Every
weight matmul then runs as a single stacked kernel pass: each W tile is
loaded once for all P probes, and with ``shared_seed`` (two_point's
antithetic ±εz pair) each z tile is regenerated once and reused for
both signs — halving weight traffic AND z-regens vs. P independent
virtual forwards, with bit-identical per-probe floats (DESIGN.md §10).

Fused virtual-perturbation runtime (DESIGN.md §10).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp

from repro.fused import matmul as pk
from repro.fused import ref as fref
from repro.obs import trace as obs

IMPLS = ("pallas", "ref")


@dataclasses.dataclass(frozen=True)
class ProbePair:
    """Static description of the stacked probes riding one forward.

    ``n`` is the probe count P (the batch axis is P·B, p-major);
    ``shared_seed`` asserts every probe draws the identical z stream
    (two_point's ±εz pair — seeds differ only in scale sign), letting
    the kernel regenerate each z tile once for all probes."""
    n: int
    shared_seed: bool = False


@dataclasses.dataclass(frozen=True)
class PerturbCtx:
    """One virtual perturbation: theta + scale * z(seed) on active layers.

    Unpaired (``pair is None``): ``seed``/``scale`` are traced scalars
    and ``masks`` maps group -> (L_g,) bool.  Paired: ``seed``/``scale``
    are (P,) vectors, ``masks`` maps group -> (P, L_g), and the model
    folds the probe axis into the batch dim (``lm_loss`` returns a (P,)
    loss vector)."""
    seed: Any                       # traced uint32 seed — scalar | (P,)
    scale: Any                      # traced f32 sign*eps — scalar | (P,)
    masks: Optional[Dict[str, Any]]  # group -> (L_g,) | (P, L_g) bool
    impl: str = "pallas"            # pallas | ref      (static)
    pair: Optional[ProbePair] = None  # stacked-probe descriptor (static)

    def group_mask(self, group: str, L: int):
        """Per-layer LeZO mask with the scan's layer axis leading:
        (L,) unpaired, (L, P) paired (the stage scan slices axis 0)."""
        if self.masks is None or group not in self.masks:
            if self.pair is None:
                return jnp.ones((L,), jnp.bool_)
            return jnp.ones((L, self.pair.n), jnp.bool_)
        m = self.masks[group]
        return m if self.pair is None else m.T

    def probe(self, i: int) -> "PerturbCtx":
        """Probe ``i`` of a paired ctx as a plain unpaired ctx — the
        per-probe escape hatch for computations that must stay literally
        the same program as the single-probe path (the chunked-CE
        reductions, whose float association is not stable across batch
        shapes under XLA fusion)."""
        if self.pair is None:
            raise ValueError("probe() requires a paired ctx")
        masks = (None if self.masks is None
                 else {g: m[i] for g, m in self.masks.items()})
        return dataclasses.replace(self, seed=self.seed[i],
                                   scale=self.scale[i], masks=masks,
                                   pair=None)

    def leaf(self, path: str) -> "LayerPerturb":
        """Handle for an always-perturbed unstacked leaf (embeddings,
        head, final norm — the leaves LeZO never drops)."""
        on = (jnp.bool_(True) if self.pair is None
              else jnp.ones((self.pair.n,), jnp.bool_))
        return LayerPerturb(self, path, jnp.uint32(0), on)

    def block(self, prefix: str, layer, active) -> "LayerPerturb":
        """Handle for layer ``layer`` of the stacked block at ``prefix``."""
        return LayerPerturb(self, prefix, layer, active)


def _count_tiles(ctx: PerturbCtx, M: int, K: int, N: int):
    """Host-side structural counters for one stacked-or-not matmul call:
    W tiles entering VMEM and z tiles regenerated.  Deterministic Python
    ints from the grid arithmetic (``matmul.grid_cells``) so the claim
    is provable on CPU where wall-clock is not; no-ops under jit tracing
    like every obs counter, so the eager bench path captures them."""
    tr = obs.get_tracer()
    if not tr.enabled or obs.tracing():
        return
    cells = pk.grid_cells(M, K, N)
    if ctx.pair is None:
        tr.count(obs.CTR_WLOAD, cells)
        tr.count(obs.CTR_ZREGEN, cells)
    else:
        tr.count(obs.CTR_WLOAD, cells)      # one load serves all P probes
        tr.count(obs.CTR_ZREGEN,
                 cells if ctx.pair.shared_seed else cells * ctx.pair.n)


@dataclasses.dataclass(frozen=True)
class LayerPerturb:
    ctx: PerturbCtx
    prefix: str                     # static leaf-path prefix
    layer: Any                      # traced uint32 index into stacked axis 0
    active: Any                     # traced bool LeZO predicate — (P,) paired

    def child(self, name: str) -> "LayerPerturb":
        return dataclasses.replace(self, prefix=self._p(name))

    def _p(self, name: str) -> str:
        if self.prefix and name:
            return f"{self.prefix}/{name}"
        return self.prefix or name

    def _seed(self, name: str):
        return fref.layer_seed(self.ctx.seed, self._p(name), self.layer)

    # ------------------------------------------------------------ shapes
    @property
    def nprobes(self) -> int:
        """Probe count P (0 = unpaired scalar ctx)."""
        return 0 if self.ctx.pair is None else self.ctx.pair.n

    def _split(self, x):
        """(P·B, ..., D) -> (P, B·..., D) — the p-major batch fold."""
        return x.reshape(self.nprobes, -1, x.shape[-1])

    # ----------------------------------------------------------- matmuls
    def matmul(self, x, w, name: str = "", *, trans: bool = False,
               ld: Optional[int] = None):
        """``x @ (w + scale*z)`` for the leaf at ``prefix/name``.  Under
        a paired ctx the probe axis rides x's leading batch dim and the
        stacked kernel runs all P probes off one pass over W."""
        seed = self._seed(name)
        if self.ctx.pair is None:
            _count_tiles(self.ctx, _rows(x), w.shape[0], w.shape[1])
            if self.ctx.impl == "ref":
                return fref.pmatmul(x, w, seed, self.ctx.scale, self.active,
                                    trans=trans, ld=ld)
            return pk.pmatmul(x, w, seed, self.ctx.scale, self.active,
                              trans=trans, ld=ld)
        lead = x.shape
        xs = self._split(x)
        _count_tiles(self.ctx, xs.shape[1], w.shape[0], w.shape[1])
        if self.ctx.impl == "ref":
            out = fref.pmatmul_stack(xs, w, seed, self.ctx.scale,
                                     self.active, trans=trans, ld=ld)
        else:
            out = pk.pmatmul_stack(xs, w, seed, self.ctx.scale, self.active,
                                   trans=trans, ld=ld,
                                   shared_seed=self.ctx.pair.shared_seed)
        return out.reshape(*lead[:-1], w.shape[1])

    # ------------------------------------------------------ vector leaves
    def vec(self, w, name: str = ""):
        """Virtually perturbed vector-sized leaf (norm scale/bias);
        paired ctx -> (P, *w.shape)."""
        seed = self._seed(name)
        if self.ctx.pair is None:
            return fref.pvec(w, seed, self.ctx.scale, self.active)
        return fref.pvec_stack(w, seed, self.ctx.scale, self.active)

    def norm(self, p: Dict[str, Any], name: str = "") -> Dict[str, Any]:
        """Perturbed view of a norm param dict ({scale[, bias]}).
        Unpaired only — paired call sites use :meth:`apply_norm` /
        :meth:`rms_norm`, which broadcast the (P, D) perturbed vectors
        against the probe-folded activations."""
        sub = self.child(name) if name else self
        return {k: sub.vec(v, k) for k, v in p.items()}

    def apply_norm(self, cfg, p: Dict[str, Any], x, name: str = ""):
        """``layers.apply_norm`` against the perturbed norm leaves.
        Paired: x is (P·B, ..., D); each probe normalizes against its
        own perturbed (D,) vector via a (P, 1, ..., D) broadcast —
        bit-identical per probe to the unpaired path (elementwise)."""
        from repro.models import layers  # local: avoid import cycle
        if self.ctx.pair is None:
            return layers.apply_norm(cfg, self.norm(p, name), x)
        sub = self.child(name) if name else self
        shp = x.shape
        xs = x.reshape(self.nprobes, -1, shp[-1])
        bc = lambda v: v[:, None, :]                  # (P, D) -> (P, 1, D)
        if cfg.norm == "rms":
            y = layers.rms_norm(xs, bc(sub.vec(p["scale"], "scale")))
        else:
            y = layers.layer_norm(xs, bc(sub.vec(p["scale"], "scale")),
                                  bc(sub.vec(p["bias"], "bias")))
        return y.reshape(shp)

    def rms_norm(self, x, w, name: str = ""):
        """``layers.rms_norm(x, w + scale*z)`` for a bare vector leaf
        (qk-norm).  Paired: per-probe perturbed vectors broadcast over
        the probe-folded leading dim."""
        from repro.models import layers
        if self.ctx.pair is None:
            return layers.rms_norm(x, self.vec(w, name))
        shp = x.shape
        xs = x.reshape(self.nprobes, -1, shp[-1])
        y = layers.rms_norm(xs, self.vec(w, name)[:, None, :])
        return y.reshape(shp)


def _rows(x) -> int:
    """Product of x's leading (non-contraction) dims."""
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return m
