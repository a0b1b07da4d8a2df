"""repro.obs telemetry suite (DESIGN.md §13).

Covers the tracing core (span nesting/ordering, the zero-allocation
disabled path, jit suppression), the sinks (JSONL round-trip, ring
bounds), the Prometheus-style metrics, the telemetry spec node's
validation, counter determinism across seeded runs, the trainer's
final-step/wall_compute logging fixes, the serving engine's metric
export, and the benchmarks/run.py tripwire gate.
"""
import json
import os
import sys

import numpy as np
import pytest

from repro import api, obs

BENCH = os.path.join(os.path.dirname(__file__), "..")
if BENCH not in sys.path:                    # for benchmarks.run import
    sys.path.insert(0, BENCH)


# ------------------------------------------------------------ span core
def test_span_nesting_ordering_and_parents():
    ring = obs.RingSink()
    tr = obs.Tracer(sinks=[ring])
    with tr.span("outer"):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    recs = ring.records()
    # completion order: children before the parent
    assert [r.name for r in recs] == ["inner_a", "inner_b", "outer"]
    outer = recs[-1]
    assert outer.depth == 0 and outer.parent == -1
    for child in recs[:2]:
        assert child.depth == 1
        assert child.parent == outer.index    # entry slot of the parent
    assert recs[0].index < recs[1].index      # entry order preserved
    assert all(r.dt >= 0 for r in recs)


def test_null_tracer_is_shared_singleton_and_free():
    assert obs.get_tracer() is obs.NULL       # default: disabled
    s1 = obs.NULL.span("anything")
    s2 = obs.NULL.span("else", meta={"k": 1})
    assert s1 is s2                           # zero-allocation fast path
    with s1 as s:
        assert s.fence("x") == "x"            # fence is identity
    obs.NULL.count("c", 5)
    obs.NULL.gauge("g", 1.0)
    assert obs.NULL.counters == {} and obs.NULL.gauges == {}
    assert not obs.NULL.enabled


def test_use_scopes_global_tracer():
    tr = obs.Tracer()
    with obs.use(tr):
        assert obs.get_tracer() is tr
        with obs.use(None):
            assert obs.get_tracer() is obs.NULL
        assert obs.get_tracer() is tr
    assert obs.get_tracer() is obs.NULL


def test_ring_sink_bounded():
    ring = obs.RingSink(capacity=3)
    tr = obs.Tracer(sinks=[ring])
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(ring) == 3
    assert [r.name for r in ring.records()] == ["s7", "s8", "s9"]
    with pytest.raises(ValueError, match="capacity"):
        obs.RingSink(capacity=0)


def test_fencing_blocks_on_result():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    ring = obs.RingSink()
    tr = obs.Tracer(sinks=[ring], fence=True)
    with tr.span("fenced") as sp:
        sp.fence(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert ring.spans("fenced")[0].dt > 0


# ----------------------------------------------------------- JSONL sink
def test_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = obs.JSONLSink(path)
    tr = obs.Tracer(sinks=[sink])
    with tr.span("a", meta={"k": 1}):
        with tr.span("b"):
            pass
    tr.count("probes", 3)
    sink.emit_event(tr.snapshot())
    sink.close()

    events = obs.read_jsonl(path)
    assert [e["type"] for e in events] == ["span", "span", "counters"]
    assert events[-1]["counters"] == {"probes": 3}
    back = obs.spans_from_jsonl(path)
    orig = [r for r in [e for e in events if e["type"] == "span"]]
    assert [r.name for r in back] == ["b", "a"]
    assert back[1].meta == {"k": 1}
    # field-level round-trip against the emitted dicts
    for rec, ev in zip(back, orig):
        assert rec.to_dict() == ev


def test_read_jsonl_tolerates_truncated_final_line(tmp_path):
    """A crash mid-append leaves a torn last line; reading the trace
    back must drop it silently — but corruption anywhere *else* in the
    file still raises (that is damage, not an interrupted write)."""
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"type": "span", "name": "a"}) + "\n")
        f.write('{"type": "span", "na')       # torn tail, no newline
    assert [e["name"] for e in obs.read_jsonl(path)] == ["a"]
    with open(path, "a") as f:                # trailing blanks don't mask it
        f.write("\n\n")
    assert [e["name"] for e in obs.read_jsonl(path)] == ["a"]
    with open(path, "a") as f:                # torn line now mid-file
        f.write(json.dumps({"type": "span", "name": "c"}) + "\n")
    with pytest.raises(json.JSONDecodeError):
        obs.read_jsonl(path)


# -------------------------------------------------------------- metrics
def test_counter_and_gauge():
    reg = obs.Registry()
    c = reg.counter("reqs", "help text")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError, match="< 0"):
        c.inc(-1)
    reg.gauge("depth").set(7)
    assert reg.gauge("depth").value == 7.0    # get-or-create returns same
    with pytest.raises(TypeError, match="already registered"):
        reg.counter("depth")


def test_histogram_cumulative_buckets_and_text():
    reg = obs.Registry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = reg.to_text()
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1.0"} 3' in text   # cumulative, not per-bucket
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text
    assert f"lat_sum {0.05 + 0.5 + 0.5 + 5.0}" in text
    assert "# TYPE lat histogram" in text and "# HELP lat latency" in text
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == float("inf")


def test_histogram_quantile_edge_cases():
    import math
    reg = obs.Registry()
    h = reg.histogram("q", buckets=(1.0, 2.0))
    assert math.isnan(h.quantile(0.5))        # no data: nan, not an edge
    with pytest.raises(ValueError, match=r"\[0, 1\]"):
        h.quantile(1.5)
    h.observe(1.5)                            # lands in the (1, 2] bucket
    # regression: q == 0 must skip empty leading buckets, not report the
    # first bucket's edge
    assert h.quantile(0.0) == 2.0
    assert h.quantile(1.0) == 2.0
    g = reg.histogram("q2", buckets=(1.0, 2.0))
    g.observe(3.0)
    g.observe(4.0)                            # everything past the last edge
    for q in (0.0, 0.5, 1.0):
        assert g.quantile(q) == float("inf")


def test_registry_exposition_deterministic():
    """Same metrics, different registration order -> identical text, so
    the Prometheus dump (and any diff over it) is byte-stable."""
    def build(order):
        reg = obs.Registry()
        ops = {"c": lambda r: r.counter("ctr", "c help").inc(3),
               "g": lambda r: r.gauge("depth").set(2.0),
               "h": lambda r: r.histogram("lat", buckets=(0.5,))
               .observe(0.25)}
        for k in order:
            ops[k](reg)
        return reg.to_text()
    assert build("cgh") == build("hgc") == build("ghc")


def test_registry_dump(tmp_path):
    reg = obs.Registry()
    reg.counter("c").inc(2)
    path = str(tmp_path / "sub" / "metrics.prom")
    reg.dump(path)
    with open(path) as f:
        assert "c 2" in f.read()


# ------------------------------------------------------- spec validation
def test_telemetry_sinks_require_enabled():
    for field, value in [("fence", True), ("jsonl", "t.jsonl"),
                         ("prometheus", "m.prom"), ("profile_dir", "p")]:
        spec = api.with_overrides(api.presets.get("tiny-smoke"),
                                  {f"telemetry.{field}": value})
        with pytest.raises(api.SpecError, match="telemetry.enabled"):
            api.validate(spec)


def test_telemetry_enabled_needs_a_sink_and_sane_ring():
    base = api.presets.get("tiny-smoke")
    with pytest.raises(api.SpecError, match="ring"):
        api.validate(api.with_overrides(
            base, {"telemetry.enabled": True, "telemetry.ring": 0}))
    with pytest.raises(api.SpecError, match="ring"):
        api.validate(api.with_overrides(base, {"telemetry.ring": -1}))
    api.validate(api.with_overrides(base, {"telemetry.enabled": True}))
    api.validate(api.with_overrides(
        base, {"telemetry.enabled": True, "telemetry.ring": 0,
               "telemetry.jsonl": "t.jsonl"}))


def test_health_knobs_require_runs_dir():
    base = api.presets.get("tiny-smoke")
    for field, value in [("run_id", "r1"), ("health_norms", True)]:
        spec = api.with_overrides(base, {f"telemetry.{field}": value})
        with pytest.raises(api.SpecError, match="telemetry.runs_dir"):
            api.validate(spec)
    api.validate(api.with_overrides(base, {
        "telemetry.runs_dir": "artifacts/runs",
        "telemetry.run_id": "r1", "telemetry.health_norms": True}))


def test_telemetry_fields_resume_mutable():
    from repro.api import spec as spec_mod
    import dataclasses
    for f in dataclasses.fields(api.Telemetry):
        assert f"telemetry.{f.name}" in spec_mod.RESUME_MUTABLE


def test_session_wiring(tmp_path):
    assert obs.session(None) is obs.NULL_SESSION
    assert obs.session(api.Telemetry()) is obs.NULL_SESSION
    assert not obs.NULL_SESSION.enabled
    obs.NULL_SESSION.flush()                  # no-ops, never raises
    path = str(tmp_path / "t.jsonl")
    sess = obs.session(api.Telemetry(enabled=True, ring=16, jsonl=path))
    assert sess.enabled and sess.ring is not None
    with sess.tracer.span("x"):
        pass
    sess.close()
    assert len(sess.ring) == 1
    assert [e["name"] for e in obs.read_jsonl(path)
            if e["type"] == "span"] == ["x"]


# --------------------------------------------- estimator instrumentation
def _toy_estimator():
    import jax.numpy as jnp
    from repro import estimators
    from repro.core import zo
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    spec = zo.build_spec(params, lambda p: None)
    cfg = estimators.EstimatorConfig(name="two_point", eps=1e-3, lr=1e-4)
    est = estimators.build_estimator(spec, cfg)
    loss = lambda p, b, perturb=None: (p["w"] * b["x"]).sum() ** 2
    batch = {"x": jnp.ones((4, 4))}
    return est, loss, params, batch


def _one_eager_step(est, loss, params, batch, seed):
    import jax.numpy as jnp
    ring = obs.RingSink()
    tr = obs.Tracer(sinks=[ring], fence=True)
    with obs.use(tr):
        p, dirs, _ = est.estimate(loss, params, batch, jnp.uint32(seed),
                                  est.init_state())
        est.apply_update(p, dirs, est.cfg.lr)
    return [r.name for r in ring.records()], dict(tr.counters)


def test_eager_step_emits_stage_spans():
    pytest.importorskip("jax")
    est, loss, params, batch = _toy_estimator()
    names, counters = _one_eager_step(est, loss, params, batch, 7)
    assert names == [obs.PERTURB, obs.FWD_PLUS, obs.PERTURB,
                     obs.FWD_MINUS, obs.UPDATE]
    assert counters[obs.CTR_PROBES] == 2
    assert counters[obs.CTR_AXPY] == 3        # perturb, perturb, fused upd
    assert counters[obs.CTR_SELECTS] == 1


def test_counters_deterministic_across_identical_seeded_runs():
    pytest.importorskip("jax")
    est, loss, params, batch = _toy_estimator()
    one = _one_eager_step(est, loss, params, batch, 42)
    two = _one_eager_step(est, loss, params, batch, 42)
    assert one == two


def test_spans_and_counters_suppressed_under_jit():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro import estimators
    est, loss, params, batch = _toy_estimator()
    step, init = estimators.make_step(loss, est.spec, est.cfg)
    jstep = jax.jit(step)
    ring = obs.RingSink()
    tr = obs.Tracer(sinks=[ring])
    with obs.use(tr):
        out = jstep(params, init(), batch, jnp.int32(0), jnp.uint32(3))
        jax.block_until_ready(out[0])
    assert len(ring) == 0 and tr.counters == {}


def test_tracing_detects_jit_and_vmap():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.obs import trace
    seen = []

    def probe(x):
        seen.append(trace.tracing())
        return x

    assert trace.tracing() is False
    jax.jit(probe)(1.0)
    jax.vmap(probe)(jnp.ones(2))
    assert seen == [True, True]


def test_profile_raises_when_trace_cannot_start(tmp_path):
    jax = pytest.importorskip("jax")
    with jax.profiler.trace(str(tmp_path / "outer")):
        # a second trace cannot start while one runs: the requested
        # device trace must fail the run, not vanish into a warning
        with pytest.raises(RuntimeError):
            with obs.profile(str(tmp_path / "inner")):
                pass


# ------------------------------------------------------------- trainer
def _tiny_trainer(**tkw):
    import warnings
    from repro.configs import opt
    from repro.data import synthetic
    from repro.train.trainer import Trainer, TrainConfig
    mcfg = opt.opt_tiny(layers=2, d_model=32, vocab=64)
    task = synthetic.TaskConfig(vocab=64, seq_len=16, n_classes=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return Trainer(mcfg, task, TrainConfig(**tkw))


def test_trainer_logs_final_step_off_grid():
    """Regression: steps % log_every != 0 silently dropped the last
    steps from history — short runs looked like they never ran."""
    h = _tiny_trainer(steps=8, batch_size=4, eval_every=0,
                      log_every=3).train()
    assert h["step"] == [0, 3, 6, 7]          # 7 == steps-1, off the grid
    assert len(h["loss"]) == len(h["wall"]) == len(h["wall_compute"]) == 4


def test_trainer_wall_compute_excludes_eval_time():
    """Regression: history['wall'] silently included eval/checkpoint
    time; wall_compute is the compute-only series."""
    h = _tiny_trainer(steps=6, batch_size=4, eval_every=2,
                      log_every=1).train()
    assert len(h["wall_compute"]) == len(h["wall"]) == 6
    assert all(wc <= w for wc, w in zip(h["wall_compute"], h["wall"]))
    # evals ran (incl. a jit compile), so the series must have diverged
    assert h["wall_compute"][-1] < h["wall"][-1]
    assert all(np.diff(h["wall_compute"]) >= 0)   # still monotone


def test_trainer_session_records_steps(tmp_path):
    path = str(tmp_path / "train.jsonl")
    spec = api.with_overrides(api.presets.get("tiny-smoke"), {
        "run.steps": 3, "run.eval_every": 0, "run.log_every": 1,
        "telemetry.enabled": True, "telemetry.jsonl": path})
    api.validate(spec)
    from repro.train.trainer import Trainer
    tr = Trainer.from_spec(spec)
    assert tr.obs.enabled
    h = tr.train()
    assert h["step"] == [0, 1, 2]             # history shape unchanged
    spans = [e for e in obs.read_jsonl(path) if e["type"] == "span"]
    assert [s["name"] for s in spans] == [obs.TRAIN_STEP] * 3
    snaps = [e for e in obs.read_jsonl(path) if e["type"] == "counters"]
    assert snaps, "flush() must append a counter snapshot"


# -------------------------------------------------------------- serving
def test_engine_exports_metrics():
    jax = pytest.importorskip("jax")
    from repro import configs, serving
    from repro.models import lm
    cfg = configs.get("opt-13b", "smoke")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sess = obs.session(api.Telemetry(enabled=True, ring=256))
    eng = serving.Engine(
        cfg, params, api.Serving(page_size=4, n_pages=32, max_lanes=2,
                                 prefill_chunk=8, max_seq=64), obs=sess)
    rng = np.random.default_rng(0)
    reqs = [serving.Request(rid=i,
                            tokens=rng.integers(0, cfg.vocab, 5).tolist(),
                            max_new_tokens=3, seed=i) for i in range(2)]
    results = eng.run(reqs)
    assert len(results) == 2
    text = eng.metrics_text()
    assert "serving_requests_completed 2" in text
    assert f"serving_tokens_generated {2 * 3}" in text
    assert "serving_ttft_seconds_count 2" in text
    assert "serving_latency_seconds_count 2" in text
    assert "serving_pages_in_use 0" in text   # drained
    assert "serving_queue_depth 0" in text
    assert "serving_lanes_active 0" in text
    for gauge in ("serving_page_utilization", "serving_page_hit_rate",
                  "serving_preemptions", "serving_cow_copies"):
        assert f"# TYPE {gauge} gauge" in text
    names = {r.name for r in sess.ring.records()}
    assert obs.SERVE_PREFILL in names and obs.SERVE_DECODE in names
    for r in results:
        assert r.ttft > 0 and r.latency >= r.ttft


def test_engine_without_session_uses_null(monkeypatch):
    jax = pytest.importorskip("jax")
    from repro import configs, serving
    from repro.models import lm
    cfg = configs.get("opt-13b", "smoke")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = serving.Engine(cfg, params,
                         api.Serving(page_size=4, n_pages=16, max_lanes=2,
                                     prefill_chunk=8, max_seq=32))
    assert eng.obs is obs.NULL_SESSION
    # metrics still exist (registry is always real), tracer is NULL
    assert not eng.obs.tracer.enabled
    assert "serving_queue_depth" in eng.metrics_text()


# ----------------------------------------------------- bench tripwires
def test_run_py_tripwire_gate(tmp_path):
    from benchmarks import run as run_mod
    ok = {"bench": "x", "tripwires": {
        "a": {"ok": True, "value": 1, "limit": 2}}}
    bad = {"bench": "y", "tripwires": {
        "b": {"ok": False, "value": 9, "limit": 2, "note": "broke"},
        "c": {"ok": True, "value": 0, "limit": 1}}}
    no_tw = {"bench": "z", "rows": []}
    assert run_mod.tripwire_failures({"A.json": ok, "C.json": no_tw}) == []
    fails = run_mod.tripwire_failures({"A.json": ok, "B.json": bad})
    assert [(a, t) for a, t, _ in fails] == [("B.json", "b")]
    # a malformed tripwire record counts as a failure, not a pass
    assert run_mod.tripwire_failures({"M.json": {"tripwires": {"t": None}}})

    # end to end through collect_artifacts off a synthetic failing file
    for name, payload in [("BENCH_ok.json", ok), ("BENCH_bad.json", bad)]:
        with open(tmp_path / name, "w") as f:
            json.dump(payload, f)
    arts = run_mod.collect_artifacts(tmp_path)
    assert sorted(arts) == ["BENCH_bad.json", "BENCH_ok.json"]
    fails = run_mod.tripwire_failures(arts)
    assert [(a, t) for a, t, _ in fails] == [("BENCH_bad.json", "b")]
