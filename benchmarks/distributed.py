"""Swarm scaling: throughput vs worker count at scalar-only traffic.

The swarm's claim (DESIGN.md §14) is that seed-synchronized ZO training
makes data parallelism nearly free on the wire: each worker ships two
float32 losses per shard and receives one ``(seed, g)`` commit, so the
per-step traffic is a few hundred bytes *independent of model size* —
against ``4·|θ|`` bytes for a first-order gradient all-reduce of the
same trainable set.

This benchmark runs the same spec (``swarm-smoke`` shapes, ``n_shards``
pinned to 4 so the reduction tree never changes) under 1, 2 and 4 local
worker processes and records:

* steps/s and measured steady-state wire bytes/step per worker count,
* the FO all-reduce baseline ``4·trainable_params`` for contrast,
* a quorum-degradation row: ``quorum=0.5`` with a chaos partition on
  one worker — the coordinator's deadline fallback commits degraded
  steps from the arrived shard subset,
* full-stream bit-identity across worker counts (the committed
  ``loss``/``projected_grad``/``seed`` trajectories must be equal to
  the bit — the decomposed sharded step makes commits a function of
  the shard set, not of who computed the shards).

Writes BENCH_dist.json with ``--check`` tripwires: steady bytes/step
under 1 KB, bit-identity across worker counts, and at least one
quorum-degraded committed step in the chaos run.
``benchmarks/run.py --check`` aggregates and gates on them.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks import common  # noqa: E402
from repro import api  # noqa: E402

# --check tripwire: scalar-only traffic.  Measured per worker *link*
# (contribution up + commit broadcast down), the same unit as the FO
# all-reduce baseline — total cluster traffic grows linearly with
# workers because the commit is broadcast, per-link it does not.
MAX_BYTES_PER_STEP = 1024
WORKER_COUNTS = (1, 2, 4)
N_SHARDS = 4                     # fixed => commits worker-count-invariant
_STREAM_KEYS = ("loss", "projected_grad", "seed", "active_layers",
                "shard_losses")


def _base_spec(steps: int) -> api.Experiment:
    spec = api.PRESETS["swarm-smoke"]
    return dataclasses.replace(
        spec,
        swarm=dataclasses.replace(spec.swarm, n_shards=N_SHARDS),
        run=dataclasses.replace(spec.run, steps=steps))


def _rows_of(runs_root: Path) -> list:
    (run_dir,) = [d for d in runs_root.iterdir() if d.is_dir()]
    with open(run_dir / "steps.jsonl") as f:
        return [json.loads(line) for line in f]


def _stream(rows: list) -> list:
    return [[row.get(k) for k in _STREAM_KEYS] for row in rows]


def _swarm_run(spec: api.Experiment, root: Path) -> dict:
    from repro.swarm import driver
    root.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    summary = driver.run_swarm(spec, runs_root=str(root))
    summary["bench_wall_s"] = time.perf_counter() - t0
    summary["rows"] = _rows_of(root)
    return summary


def run(smoke: bool = False, json_path: str = None, check: bool = False):
    from repro.swarm import driver, shardstep
    # this parent coordinates; only its worker processes may hold chips
    driver.pin_parent_to_cpu()
    steps = 6 if smoke else 10
    spec = _base_spec(steps)
    fo_bytes = 4 * shardstep.trainable_param_count(spec)

    tmp = Path(tempfile.mkdtemp(prefix="bench_dist_"))
    rows, scaling, streams = [], {}, {}
    try:
        for w in WORKER_COUNTS:
            s = dataclasses.replace(
                spec, swarm=dataclasses.replace(spec.swarm, workers=w))
            summary = _swarm_run(s, tmp / f"w{w}")
            streams[w] = _stream(summary["rows"])
            bps = summary["steady_bytes_per_step"] / w
            scaling[str(w)] = {
                "workers": w,
                "steps_per_s": steps / summary["wall_s"],
                "wall_s": summary["wall_s"],
                "steady_bytes_per_step_per_link": bps,
                "steady_bytes_per_step_total": summary[
                    "steady_bytes_per_step"],
                "total_wire_bytes": summary["wire_bytes"],
                "membership_epochs": summary["membership_epochs"],
            }
            rows.append((f"swarm_w{w}", summary["wall_s"] / steps * 1e6,
                         f"{bps:.0f} B/step/link "
                         f"({fo_bytes / max(bps, 1):.0f}x under FO "
                         "all-reduce)"))

        # quorum fallback: partition one worker for a step window; the
        # deadline commits from the arrived shard subset at quorum=0.5
        qspec = dataclasses.replace(
            spec, swarm=dataclasses.replace(
                spec.swarm, workers=2, quorum=0.5, step_deadline_s=1.0,
                chaos_seed=7, chaos_partition=f"1:2-{steps - 2}"))
        qsum = _swarm_run(qspec, tmp / "quorum")
        degraded = sum(1 for r in qsum["rows"]
                       if 0 in (r.get("arrived") or []))
        scaling["quorum_degraded"] = {
            "workers": 2, "quorum": 0.5,
            "degraded_steps": degraded,
            "straggler_steps": qsum["straggler_steps"],
            "steady_bytes_per_step_per_link":
                qsum["steady_bytes_per_step"] / 2,
        }
        rows.append(("swarm_quorum0.5_partition",
                     qsum["wall_s"] / steps * 1e6,
                     f"{degraded}/{steps} steps committed degraded"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    identical = all(streams[w] == streams[WORKER_COUNTS[0]]
                    for w in WORKER_COUNTS)
    worst_bps = max(s["steady_bytes_per_step_per_link"]
                    for s in scaling.values())
    rows.append(("fo_allreduce_baseline", 0.0,
                 f"{fo_bytes} B/step (4*trainable_params)"))
    rows.append(("bit_identity_1_2_4", 0.0, str(identical)))
    common.emit(rows)

    if json_path:
        common.write_json(json_path, {
            "bench": "distributed", "n_shards": N_SHARDS, "steps": steps,
            "scaling": scaling,
            "fo_allreduce_bytes_per_step": fo_bytes,
            "bit_identical_across_worker_counts": identical,
            "tripwires": {
                "swarm_bytes_per_step": {
                    "ok": worst_bps < MAX_BYTES_PER_STEP,
                    "value": worst_bps, "limit": MAX_BYTES_PER_STEP,
                    "note": "steady-state wire bytes per committed step "
                            "per worker link (scalar-only sync broken "
                            "above this)"},
                "swarm_bit_identity": {
                    "ok": identical, "value": identical, "limit": True,
                    "note": "committed scalar streams must match to the "
                            "bit across 1/2/4 workers"},
                "swarm_quorum_degraded": {
                    "ok": degraded >= 1, "value": degraded, "limit": 1,
                    "note": "partition run must commit >=1 step from a "
                            "partial shard set (deadline fallback dead "
                            "otherwise)"},
            },
        }, spec=spec)
    if check:
        problems = []
        if worst_bps >= MAX_BYTES_PER_STEP:
            problems.append(f"bytes/step {worst_bps:.0f} >= "
                            f"{MAX_BYTES_PER_STEP}")
        if not identical:
            problems.append("streams differ across worker counts")
        if degraded < 1:
            problems.append("no quorum-degraded step committed")
        if problems:
            raise SystemExit("distributed bench tripwires: "
                             + "; ".join(problems))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_dist.json here")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when a swarm tripwire fails "
                         "(bytes/step, bit-identity, quorum fallback)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke, json_path=args.json, check=args.check)


if __name__ == "__main__":
    main()
