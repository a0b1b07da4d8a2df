"""Optional ``jax.profiler`` trace hook behind ``telemetry.profile_dir``.

The obs spans answer "which *stage* is slow"; when the question drops to
"which *op* inside the stage", the real profiler takes over.  ``with
obs.profile(dir):`` wraps a region in ``jax.profiler.trace`` when a
directory is given and is a free no-op otherwise, so call sites (the
trainer loop, the serving drain, the step benchmark) carry exactly one
line regardless of configuration.  A trace that was asked for and cannot
start or stop raises: a run that requested a device trace never ends
successfully without one (DESIGN.md §13).
"""
from __future__ import annotations

import contextlib
from typing import Optional


@contextlib.contextmanager
def profile(profile_dir: Optional[str]):
    """``jax.profiler.trace(profile_dir)`` when a dir is given, else a
    no-op.  Profiler start/stop failures propagate."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        yield
