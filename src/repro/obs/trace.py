"""Span/Tracer core: stage-level step tracing for every hot path.

The paper's headline result is an *observability* result — >50% of a
MeZO step sits in the perturb/update sweeps — so the repo carries one
shared tracing layer instead of per-benchmark stopwatch code.  A
:class:`Tracer` records nestable :class:`SpanRecord`s on a monotonic
``perf_counter`` clock, with explicit ``block_until_ready`` *fencing*
(``Span.fence``) so device-async dispatch cannot lie about where time
went, plus named counters/gauges for structural facts (probes
evaluated, axpy sweeps, active layers under LeZO sparsity).  An enabled
span also opens a ``jax.profiler.TraceAnnotation`` named
``repro.<span>``, so under a profiler the program's host spans share the
device trace's clock.

Three rules keep the hot paths honest (DESIGN.md §13):

  * **Disabled means free.**  The default tracer is :data:`NULL`, whose
    ``span``/``count``/``gauge`` are no-ops returning one shared
    singleton — no record, no ``Span``, no sink call is ever allocated.
    Instrumented code calls ``obs.get_tracer()`` unconditionally.
  * **Never record under jit tracing.**  Instrumentation lives inside
    functions that callers may ``jax.jit``; a span timed at trace time
    would record compile-walk time once per cache entry.  ``span`` and
    ``count`` therefore no-op whenever jax reports an active trace, so
    jitted steps stay clean and the same code path yields real stage
    timings when run eagerly (the staged-measurement mode
    ``benchmarks/step_time.py`` uses).  The ZO step's stages go through
    :func:`stage` instead: under tracing it names the compiled
    program's ops (``jax.named_scope``), so the device trace can
    attribute each op to its stage; eagerly it is a span.
  * **Fence when asked.**  ``Tracer(fence=True)`` makes ``Span.fence``
    call ``jax.block_until_ready`` on the span's result before the
    clock stops; with ``fence=False`` the same call is free, so
    steady-state pipelines keep their async dispatch.

Stage taxonomy (the ZO step's named stages) is defined here so every
emitter and every consumer agrees on the strings.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

# ------------------------------------------------------- stage taxonomy
# One MeZO/LeZO step decomposes into these named stages (DESIGN.md §13).
# `perturb` appears twice per materialized two-point step (+eps, -2eps)
# and zero times under the virtual forward backend (repro.fused).
PERTURB = "perturb"
FWD_PLUS = "forward+εz"
FWD_MINUS = "forward-εz"
FWD_PAIR = "forward_pair"     # one paired ±εz forward (fused probe stack)
FWD_BASE = "forward"          # one_sided's unperturbed baseline forward
UPDATE = "update_axpy"
LOSS_HEAD = "loss_head"       # the LM head + cross-entropy, inside a probe
# The MoE FFN's parts, scopes inside a probe (models.moe), outside STAGES:
# a stage split counts their ops under the probe they run in.
MOE_DISPATCH = "moe_dispatch"  # router, top-k, sort by expert, row gather
MOE_EXPERTS = "moe_experts"    # the grouped expert matmuls + activation
MOE_COMBINE = "moe_combine"    # routing weights + scatter-add to tokens
TRAIN_STEP = "train/step"     # the trainer's whole-step record (jit-safe)
SERVE_PREFILL = "serve/prefill"
SERVE_DECODE = "serve/decode"
STAGES: Tuple[str, ...] = (PERTURB, FWD_PLUS, FWD_MINUS, FWD_PAIR, UPDATE)
ANNOTATION_PREFIX = "repro."  # host spans' names in a profiler trace

# Counter names (structural per-run facts, deterministic under a seed).
CTR_PROBES = "probes_evaluated"
CTR_AXPY = "axpy_sweeps"
CTR_AXPY_ROWS = "axpy_rows_swept"   # stacked-leaf rows a sweep touches
CTR_SELECTS = "layer_selections"
CTR_MOE_ROUTED = "moe_assignments_routed"  # token-expert pairs computed
CTR_MOE_GMM_ROWS = "moe_gmm_rows_computed"  # grouped-matmul kernel tile rows
CTR_LOSS_HEAD_ROWS = "loss_head_rows"  # B x the loss head's window, a probe
# Fused-forward W-traffic counters (repro.fused): VMEM tile loads of
# weight matrices and z-tile regenerations per step — the structural
# numbers the paired ±εz probe halves (counted host-side from the same
# grid arithmetic the kernel runs, so ref and pallas impls agree).
CTR_WLOAD = "w_tile_loads"
CTR_ZREGEN = "z_regens"
GAUGE_ACTIVE = "active_layers"


def tracing() -> bool:
    """True while jax is tracing (jit/vmap/grad) — spans and counters
    must not record then.  jax is imported lazily so this module stays
    importable without it.  Public so instrumentation sites that must
    concretize a value (e.g. ``int(n_active)`` for a gauge) can skip
    the whole block under tracing."""
    from jax._src import core
    return not core.trace_state_clean()


_tracing = tracing


@dataclasses.dataclass
class SpanRecord:
    """One finished span.  ``index`` is the emission sequence number
    (completion order); ``parent`` the index of the enclosing span's
    *entry* slot (-1 at top level); ``depth`` the nesting level."""
    name: str
    t0: float
    dt: float
    depth: int
    index: int
    parent: int
    meta: Optional[Dict[str, Any]] = None

    def to_dict(self) -> Dict[str, Any]:
        d = {"type": "span", "name": self.name, "t0": self.t0,
             "dt": self.dt, "depth": self.depth, "index": self.index,
             "parent": self.parent}
        if self.meta:
            d["meta"] = self.meta
        return d


class Span:
    """A live span; use as a context manager.  ``fence(x)`` marks ``x``
    (any pytree of jax arrays) as the span's result: when the owning
    tracer fences, the clock stops only after ``x`` is device-ready."""

    __slots__ = ("_tracer", "name", "meta", "_t0", "_result", "_entry",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str,
                 meta: Optional[Dict[str, Any]] = None):
        self._tracer = tracer
        self.name = name
        self.meta = meta
        self._result = None

    def fence(self, result):
        self._result = result
        return result

    def __enter__(self) -> "Span":
        import jax
        self._entry = self._tracer._enter()
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._tracer.fence and self._result is not None:
            import jax
            jax.block_until_ready(self._result)
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._exit(self, dt)
        self._result = None
        return False


class _NullSpan:
    """The shared do-nothing span: one instance for the whole process,
    so a disabled tracer's hot path allocates nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def fence(self, result):
        return result


_NULL_SPAN = _NullSpan()


class _Scope:
    """A stage inside a traced program: ``jax.named_scope(name)`` with
    the ``Span`` API, so emitters need not know whether they trace."""

    __slots__ = ("_scope",)

    def __init__(self, name: str):
        import jax
        self._scope = jax.named_scope(name)

    def __enter__(self) -> "_Scope":
        self._scope.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        return self._scope.__exit__(exc_type, exc, tb)

    def fence(self, result):
        return result


def stage(name: str):
    """Open the ZO step's stage ``name`` (``with obs.stage(obs.PERTURB)
    as sp: x = sp.fence(...)``).

    While jax traces, whatever tracer is installed, the stage is a
    ``jax.named_scope``: its name lands in the ``op_name`` metadata of
    every op traced inside it, and the TPU profiler reports that path
    as the op's ``tf_op``, so a device trace attributes each op of the
    compiled step to its innermost stage.  Eagerly it is the installed
    tracer's span (free under :data:`NULL`)."""
    if _tracing():
        return _Scope(name)
    return get_tracer().span(name)


class Tracer:
    """Records spans/counters into pluggable sinks (repro.obs.sinks).

    ``sinks``: objects with ``emit(record: SpanRecord)``.
    ``fence``: block on each span's fenced result before timing exit
    (true stage timings; off for steady-state pipelines).
    """

    enabled = True

    def __init__(self, sinks=(), fence: bool = False):
        self.sinks = list(sinks)
        self.fence = fence
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, float] = {}
        self._depth = 0
        self._index = 0
        self._stack: List[int] = []   # entry indices of open spans

    # ------------------------------------------------------------ spans
    def span(self, name: str, meta: Optional[Dict[str, Any]] = None):
        if _tracing():
            return _NULL_SPAN
        return Span(self, name, meta)

    def _enter(self) -> int:
        entry = self._index
        self._index += 1
        self._stack.append(entry)
        self._depth += 1
        return entry

    def _exit(self, span: Span, dt: float):
        self._depth -= 1
        self._stack.pop()
        parent = self._stack[-1] if self._stack else -1
        rec = SpanRecord(name=span.name, t0=span._t0, dt=dt,
                         depth=self._depth, index=span._entry,
                         parent=parent, meta=span.meta)
        for s in self.sinks:
            s.emit(rec)

    # --------------------------------------------------------- counters
    def count(self, name: str, n: int = 1):
        if _tracing():
            return
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value):
        if _tracing():
            return
        self.gauges[name] = value

    def snapshot(self) -> Dict[str, Any]:
        """Counters + gauges as one JSON-ready event."""
        return {"type": "counters", "counters": dict(self.counters),
                "gauges": dict(self.gauges)}

    def reset(self):
        self.counters.clear()
        self.gauges.clear()


class NullTracer(Tracer):
    """The disabled tracer: every operation is a no-op and ``span``
    returns the process-wide :data:`_NULL_SPAN` singleton — the
    zero-allocation fast path the test suite pins by identity."""

    enabled = False

    def __init__(self):
        super().__init__(sinks=(), fence=False)

    def span(self, name: str, meta=None):
        return _NULL_SPAN

    def count(self, name: str, n: int = 1):
        pass

    def gauge(self, name: str, value):
        pass


NULL = NullTracer()
_CURRENT: Tracer = NULL


def get_tracer() -> Tracer:
    return _CURRENT


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install ``tracer`` (None -> NULL) globally; returns the previous
    one so callers can restore it."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tracer if tracer is not None else NULL
    return prev


class use:
    """``with obs.use(tracer): ...`` — scope the global tracer."""

    def __init__(self, tracer: Optional[Tracer]):
        self._tracer = tracer

    def __enter__(self) -> Tracer:
        self._prev = set_tracer(self._tracer)
        return _CURRENT

    def __exit__(self, exc_type, exc, tb):
        set_tracer(self._prev)
        return False
