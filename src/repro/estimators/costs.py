"""Analytic per-step cost counts for ZO gradient estimators.

Pure Python (no jax) so the HLO cost model in ``launch/analysis.py`` and
the dry-run roofline can import it without touching an accelerator
runtime.  Counts are per optimization step:

  * ``forwards``      — model forward passes.  ``one_sided`` issues its q
                        perturbed evaluations as ONE vmapped (widened)
                        forward, but compute/HBM cost still scales with q,
                        so we count q + 1 (the +1 is the shared baseline).
  * ``axpy_sweeps``   — full parameter-sweep axpy passes (perturb /
                        restore / update).  Each sweep reads + writes every
                        *active* parameter byte once.
  * ``state_scalars`` — optimizer state beyond the parameters themselves,
                        in floats.  ``num_layers`` enters only for the
                        importance wrapper (its smoothed per-layer scores).

These counts are the contract the estimator implementations must honor
(asserted in tests/test_estimators.py) — they are what keeps the memory
story "params + O(q) scalars" auditable.

Estimator subsystem (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Dict

ESTIMATORS = ("two_point", "one_sided", "averaged", "importance")
FORWARD_MODES = ("materialized", "virtual", "virtual_ref")

# Baseline the lowered train graph corresponds to (launch/specs.py lowers
# a fused two-point step: 2 forwards + 3 axpy sweeps).
BASELINE = "two_point"


def step_counts(name: str, q: int = 1, fused_update: bool = True,
                inner: str = "two_point", num_layers: int = 0,
                forward_backend: str = "materialized") -> Dict:
    """Per-step cost counts for estimator ``name`` with ``q`` directions.

    ``forward_backend="virtual"``/``"virtual_ref"`` (the fused runtime,
    DESIGN.md §10) evaluates every probe against virtually perturbed
    weights: all perturb/restore sweeps vanish and only the update axpy
    passes remain — the forward count is unchanged (probes still run).
    """
    if q < 1:
        raise ValueError(f"q must be >= 1, got {q}")
    if forward_backend not in FORWARD_MODES:
        raise ValueError(f"unknown forward_backend {forward_backend!r}; "
                         f"pick from {FORWARD_MODES}")
    virtual = forward_backend != "materialized"
    if name == "two_point":
        # perturb(+eps), perturb(-2eps), then fused restore+update — or
        # separate restore and update passes when unfused.  Virtual: the
        # probes are fused forwards, leaving only the single update axpy.
        sweeps = 1 if virtual else (3 if fused_update else 4)
        return {"forwards": 2, "axpy_sweeps": sweeps, "state_scalars": 0}
    if name == "one_sided":
        # 1 baseline + q perturbed forwards (one widened vmapped launch);
        # q perturb sweeps happen inside the vmap (zero when virtual:
        # the probes are q seeds of the same weights), q update sweeps.
        return {"forwards": q + 1, "axpy_sweeps": q if virtual else 2 * q,
                "state_scalars": 0}
    if name == "averaged":
        # q independent two-point probes (3 sweeps each: +eps, -2eps,
        # +eps restore; zero when virtual) + q update sweeps.
        return {"forwards": 2 * q, "axpy_sweeps": q if virtual else 4 * q,
                "state_scalars": 0}
    if name == "importance":
        if inner == "importance":
            raise ValueError("importance cannot wrap itself")
        c = dict(step_counts(inner, q=q, fused_update=fused_update,
                             forward_backend=forward_backend))
        c["state_scalars"] = c["state_scalars"] + num_layers
        return c
    raise ValueError(f"unknown estimator {name!r}; pick from {ESTIMATORS}")
