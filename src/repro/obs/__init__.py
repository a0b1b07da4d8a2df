"""``repro.obs`` — stage-level tracing + serving metrics (DESIGN.md §13).

One lightweight telemetry subsystem used by every hot path:

  * :mod:`~repro.obs.trace` — ``Span``/``Tracer`` with monotonic
    ``perf_counter`` timing, explicit ``block_until_ready`` fencing,
    nesting, a zero-allocation disabled path, and the named ZO step
    stages (``perturb`` / ``forward+εz`` / ``forward-εz`` /
    ``update_axpy`` / ``loss_head``, opened by ``stage``) plus counters
    for probes, axpy sweeps and active layers under LeZO sparsity.
  * :mod:`~repro.obs.sinks` — in-memory ring buffer + JSONL event log.
  * :mod:`~repro.obs.metrics` — Prometheus-style counters / gauges /
    histograms with a text exposition dump (the serving engine's queue
    depth, lane occupancy, page utilization, TTFT/latency).
  * :mod:`~repro.obs.profiler` — optional ``jax.profiler`` region
    behind ``telemetry.profile_dir``.
  * :mod:`~repro.obs.runtime` — ``session(spec.telemetry)`` wiring.
  * :mod:`~repro.obs.health` — sync-free per-step ZO optimizer vitals
    (seed lineage, projected gradient g, ε/lr, LeZO layer coverage,
    update magnitudes) drained in one batched transfer at ``log_every``.
  * :mod:`~repro.obs.runlog` — structured ``artifacts/runs/<run_id>/``
    directories (spec + JSONL step stream + summary) that ``launch
    report`` renders and ``launch replay`` re-executes bit-identically.

Emitters call ``obs.get_tracer()`` unconditionally; the default is the
disabled :data:`NULL` tracer, whose operations are free, and spans are
automatically suppressed while jax traces a jit — so instrumentation
costs nothing on compiled steady-state paths and yields real stage
timings when the same code runs eagerly (``benchmarks/step_time.py``).
The ZO step's stages (``stage``) name the compiled program's ops under
jit instead, so a device trace attributes each op to its stage.
"""
from repro.obs.health import HealthAccumulator
from repro.obs.metrics import (Counter, Gauge, Histogram, LATENCY_BUCKETS,
                               Registry)
from repro.obs.profiler import profile
from repro.obs.runlog import (DEFAULT_RUNS_DIR, RunDir, RunLog, list_runs,
                              load_run, make_run_id, resolve_run)
from repro.obs.runtime import NULL_SESSION, Session, session
from repro.obs.sinks import (JSONLSink, RingSink, read_jsonl,
                             spans_from_jsonl)
from repro.obs.trace import (CTR_AXPY, CTR_PROBES, CTR_SELECTS,
                             CTR_WLOAD, CTR_ZREGEN, FWD_BASE, FWD_MINUS,
                             FWD_PAIR, FWD_PLUS, GAUGE_ACTIVE, LOSS_HEAD,
                             NULL, PERTURB, SERVE_DECODE, SERVE_PREFILL,
                             STAGES, Span, SpanRecord, TRAIN_STEP, Tracer,
                             UPDATE, get_tracer, set_tracer, stage, tracing,
                             use)

__all__ = [
    "CTR_AXPY", "CTR_PROBES", "CTR_SELECTS", "CTR_WLOAD", "CTR_ZREGEN",
    "Counter", "DEFAULT_RUNS_DIR", "FWD_BASE", "FWD_MINUS", "FWD_PAIR",
    "FWD_PLUS", "GAUGE_ACTIVE", "Gauge", "HealthAccumulator", "Histogram",
    "JSONLSink", "LATENCY_BUCKETS", "LOSS_HEAD", "NULL", "NULL_SESSION",
    "PERTURB", "Registry", "RingSink", "RunDir", "RunLog", "SERVE_DECODE",
    "SERVE_PREFILL", "STAGES", "Session", "Span", "SpanRecord",
    "TRAIN_STEP", "Tracer", "UPDATE", "get_tracer", "list_runs",
    "load_run", "make_run_id", "profile", "read_jsonl", "resolve_run",
    "session", "set_tracer", "spans_from_jsonl", "stage", "tracing", "use",
]
