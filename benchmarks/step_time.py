"""Stage-level ZO step decomposition, measured through repro.obs spans.

The paper's headline observability claim is that a MeZO step spends the
majority of its wall time in the perturb/update parameter sweeps, not
the forwards — and that the virtual (fused-forward) runtime removes the
perturb sweeps entirely.  This benchmark measures that decomposition
with the *production instrumentation* rather than bespoke stopwatches:
the estimator step runs eagerly under a fencing ``obs.Tracer`` (spans
no-op inside jit, so eager execution is the staged-measurement mode —
DESIGN.md §13), and the per-stage shares come straight out of the ring
buffer the trainer itself would use.

Three measurements per forward backend (materialized, virtual_ref):

  * eager staged profile — median per-stage seconds + share of step,
    plus the deterministic per-step counters (axpy sweeps, probes, RNG
    folds) that pin the structural claim (3 sweeps -> 1 under virtual);
  * jitted step time — the real training throughput number;
  * telemetry overhead — the jitted step timed with the default NULL
    tracer vs an installed active tracer.  All instrumentation either
    no-ops under jit tracing or lives outside the compiled step, so the
    ratio must stay ~1; the tripwire allows 25% for CI noise.

Writes ``BENCH_step.json`` with a ``tripwires`` block that
``benchmarks/run.py --check`` (and this script's own ``--check``)
turns into a CI gate; ``--jsonl`` additionally writes a sample span
trace (the artifact CI uploads next to the JSON).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import (emit, make_batch, rows_to_json,  # noqa: E402
                               timeit, write_json)
from repro import api, estimators, obs  # noqa: E402
from repro.core import zo  # noqa: E402
from repro.models import lm  # noqa: E402

FORWARDS = ("materialized", "virtual_ref")
# Stages each backend must emit: virtual probes never write parameters,
# so a virtual step has no perturb spans at all — and with paired probes
# (the default) the ±εz pair rides ONE forward_pair span instead of the
# forward+εz / forward-εz pair (the structural claim this PR adds).
EXPECTED_STAGES = {
    "materialized": (obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS, obs.UPDATE),
    "virtual_ref": (obs.FWD_PAIR, obs.UPDATE),
}
# axpy sweeps per step: perturb + perturb + fused restore+update vs the
# single virtual update pass (estimators/costs.py derives the same).
EXPECTED_SWEEPS = {"materialized": 3, "virtual_ref": 1}
MAX_OVERHEAD_RATIO = 1.25   # jit step, tracer installed vs NULL
MIN_OVERHEAD_RATIO = 0.80   # a ratio well under 1.0 means the baseline
                            # series absorbed compile/warmup cost instead


def _parts(mcfg, espec, fb):
    params = lm.init_params(mcfg, jax.random.PRNGKey(0))
    spec = zo.build_spec(params, lm.zo_group_fn)
    ecfg = dataclasses.replace(api.derive(espec).est_cfg,
                               forward_backend=fb)
    loss_fn = lambda p, b, perturb=None: lm.lm_loss(mcfg, p, b,
                                                    perturb=perturb)
    est = estimators.build_estimator(spec, ecfg)
    step, init = estimators.make_step(loss_fn, spec, ecfg)
    return params, est, loss_fn, ecfg, jax.jit(step), init


def _median(xs):
    return float(np.median(np.asarray(xs))) if xs else 0.0


def stage_profile(est, loss_fn, params, batch, iters, jsonl_path=None):
    """Run the instrumented estimator step eagerly under a fencing
    tracer; aggregate the ring buffer into per-stage medians/shares."""
    ring = obs.RingSink()
    sinks = [ring]
    jsonl = None
    if jsonl_path:
        jsonl = obs.JSONLSink(jsonl_path)
        sinks.append(jsonl)
    tr = obs.Tracer(sinks=sinks, fence=True)
    with obs.use(tr):
        for i in range(iters + 1):           # +1 warmup iteration
            if i == 1:                        # drop warmup spans/counters
                ring.clear()
                tr.reset()
            with tr.span(obs.TRAIN_STEP) as sp:
                p, dirs, _ = est.estimate(loss_fn, params, batch,
                                          jnp.uint32(i + 1), est.init_state())
                sp.fence(est.apply_update(p, dirs, est.cfg.lr))
    if jsonl is not None:
        jsonl.emit_event(tr.snapshot())
        jsonl.close()
    step_s = _median([r.dt for r in ring.spans(obs.TRAIN_STEP)])
    stages = {}
    for name in (obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS, obs.FWD_PAIR,
                 obs.FWD_BASE, obs.UPDATE):
        recs = ring.spans(name)
        if not recs:
            continue
        per_step = sum(r.dt for r in recs) / iters
        stages[name] = {"s": per_step,
                        "share": per_step / step_s if step_s else 0.0,
                        "spans_per_step": len(recs) / iters}
    counters = {k: v / iters for k, v in tr.counters.items()}
    return {"step_s": step_s, "stages": stages, "counters": counters}


def measure_overhead(step, init, params, batch, iters):
    """Jitted step under the NULL tracer vs an installed active tracer:
    recording is suppressed inside jit, so the compiled path is shared
    and the ratio pins the <2% disabled-telemetry claim (with noise
    headroom).

    The step is fully warmed (compile + first-touch allocations) BEFORE
    either series, and the two series interleave sample-by-sample, so
    neither side absorbs one-time cost or drift the other skips — the
    previous back-to-back ordering timed the disabled series first on a
    cold cache and reported ratios like 0.59x, which is telemetry making
    the step *faster*, i.e. a measurement artifact, not a result."""
    import time
    args = (params, init(), batch, jnp.int32(0), jnp.uint32(1))
    for _ in range(2):                       # compile + steady-state warm
        jax.block_until_ready(step(*args))
    tr = obs.Tracer(sinks=[obs.RingSink()], fence=False)
    off, on = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        off.append(time.perf_counter() - t0)
        with obs.use(tr):
            t0 = time.perf_counter()
            jax.block_until_ready(step(*args))
            on.append(time.perf_counter() - t0)
    t_off, t_on = _median(off), _median(on)
    return {"disabled_s": t_off, "enabled_s": t_on,
            "ratio": t_on / t_off if t_off else 1.0}


def measure_health_overhead(step, init, params, batch, iters, num_layers):
    """Jitted step alone vs jitted step + ``HealthAccumulator.record``
    (with a drain every 8 steps — the log_every cadence the trainer
    uses): record() only buffers device references, so the ratio pins
    the claim that per-step health telemetry never syncs the device.
    Same interleaved, pre-warmed protocol as ``measure_overhead``."""
    import time
    args = (params, init(), batch, jnp.int32(0), jnp.uint32(1))
    for _ in range(2):                       # compile + steady-state warm
        jax.block_until_ready(step(*args))
    acc = obs.HealthAccumulator(num_layers)
    off, on = [], []
    for i in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = step(*args)
        acc.record(i, out[2], seed=i)
        if (i + 1) % 8 == 0:
            acc.drain()
        jax.block_until_ready(out)
        on.append(time.perf_counter() - t0)
    acc.drain()
    t_off, t_on = _median(off), _median(on)
    return {"disabled_s": t_off, "enabled_s": t_on,
            "ratio": t_on / t_off if t_off else 1.0,
            "steps_recorded": acc.summary()["steps_recorded"]}


def build_tripwires(backends, overhead, health):
    """-> {name: {ok, value, limit, note}} — the convention run.py
    --check collects across every BENCH_*.json artifact."""
    tw = {}
    for fb, rec in backends.items():
        seen = set(rec["eager"]["stages"])
        want = set(EXPECTED_STAGES[fb])
        extra = (seen - want - {obs.FWD_BASE}) if fb == "materialized" \
            else (seen & {obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS})
        tw[f"stages_{fb}"] = {
            "ok": want <= seen and not extra,
            "value": sorted(seen), "limit": sorted(want),
            "note": "every expected stage span present"
                    + ("" if fb == "materialized"
                       else " and no perturb sweep or split ±εz forwards"
                            " under virtual (paired probes ride one"
                            " forward_pair span)")}
        sweeps = rec["eager"]["counters"].get(obs.CTR_AXPY, 0)
        tw[f"axpy_sweeps_{fb}"] = {
            "ok": sweeps == EXPECTED_SWEEPS[fb],
            "value": sweeps, "limit": EXPECTED_SWEEPS[fb],
            "note": "parameter sweeps per step (3 materialized -> "
                    "1 virtual is the paper's structural claim)"}
    tw["telemetry_overhead"] = {
        "ok": (MIN_OVERHEAD_RATIO <= overhead["ratio"]
               <= MAX_OVERHEAD_RATIO),
        "value": overhead["ratio"],
        "limit": [MIN_OVERHEAD_RATIO, MAX_OVERHEAD_RATIO],
        "note": "jitted step, active tracer vs NULL (must be ~1: spans "
                "no-op inside jit; well under 1 means the disabled "
                "baseline absorbed warmup cost)"}
    tw["health_overhead"] = {
        "ok": (MIN_OVERHEAD_RATIO <= health["ratio"]
               <= MAX_OVERHEAD_RATIO),
        "value": health["ratio"],
        "limit": [MIN_OVERHEAD_RATIO, MAX_OVERHEAD_RATIO],
        "note": "jitted step + HealthAccumulator record/drain vs plain "
                "(must be ~1: record buffers device refs without sync)"}
    return tw


def run(smoke=False, json_path=None, preset="bench-smoke", jsonl_path=None,
        check=False):
    espec = api.presets.get(preset)
    d = api.derive(espec)
    mcfg, seq = d.model_cfg, espec.model.seq_len
    batch = make_batch(mcfg, espec.run.batch_size if smoke else 16, seq)
    eager_iters = 2 if smoke else 4
    jit_iters = 3 if smoke else 5

    rows, backends = [], {}
    for fb in FORWARDS:
        params, est, loss_fn, ecfg, step, init = _parts(mcfg, espec, fb)
        eager = stage_profile(est, loss_fn, params, batch, eager_iters,
                              jsonl_path=(jsonl_path
                                          if fb == "materialized" else None))
        t_jit = timeit(lambda: step(params, init(), batch, jnp.int32(0),
                                    jnp.uint32(1)),
                       warmup=1, iters=jit_iters)
        backends[fb] = {"eager": eager, "jit_step_s": t_jit}
        # one row per measurement mode, each derived field describing
        # ITS OWN number (the old single row was named steptime_jit_*
        # but carried an "eager ... us" derived label)
        rows.append((f"steptime_jit_{fb}", t_jit * 1e6,
                     "jitted step (compiled, tracer-free)"))
        rows.append((f"steptime_eager_{fb}", eager["step_s"] * 1e6,
                     "eager staged step (fencing tracer installed)"))
        for name, st in eager["stages"].items():
            rows.append((f"stage_{fb}_{name}", st["s"] * 1e6,
                         f"{st['share'] * 100:.0f}% of eager step"))
    # overhead measured once, on the materialized jitted step
    params, est_m, _, _, step, init = _parts(mcfg, espec, "materialized")
    overhead = measure_overhead(step, init, params, batch, jit_iters)
    rows.append(("telemetry_overhead_ratio", 0.0,
                 f"{overhead['ratio']:.3f}x (enabled/disabled, jit)"))
    health = measure_health_overhead(step, init, params, batch, jit_iters,
                                     est_m.spec.num_layers)
    rows.append(("health_overhead_ratio", 0.0,
                 f"{health['ratio']:.3f}x (record+drain/plain, jit)"))

    sweep_share = sum(
        st["s"] for n, st in backends["materialized"]["eager"]["stages"]
        .items() if n in (obs.PERTURB, obs.UPDATE))
    ms = backends["materialized"]["eager"]["step_s"]
    rows.append(("perturb_update_share", 0.0,
                 f"{sweep_share / ms * 100:.0f}% of materialized eager step"
                 if ms else "n/a"))

    emit(rows)
    tripwires = build_tripwires(backends, overhead, health)
    if json_path:
        write_json(json_path, {
            "bench": "step_time",
            "model": mcfg.name,
            "stages": list(obs.STAGES),
            "backends": backends,
            "perturb_update_share": sweep_share / ms if ms else None,
            "telemetry_overhead": overhead,
            "health_overhead": health,
            "tripwires": tripwires,
            "rows": rows_to_json(rows),
        }, spec=espec)
    bad = {k: v for k, v in tripwires.items() if not v["ok"]}
    if check and bad:
        for k, v in bad.items():
            print(f"TRIPWIRE {k}: value={v['value']!r} "
                  f"limit={v['limit']!r} ({v['note']})", file=sys.stderr)
        raise SystemExit(f"step_time: {len(bad)} tripwire(s) failed")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--preset", default="bench-smoke",
                    help="experiment spec preset (repro.api.presets)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_step.json trajectory here")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="write a sample span trace (JSONL) here")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when any tripwire fails")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke, json_path=args.json, preset=args.preset,
        jsonl_path=args.jsonl, check=args.check)


if __name__ == "__main__":
    main()
