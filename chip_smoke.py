#!/usr/bin/env python3
"""Bring-up smoke of both main paths on a TPU, at OPT-1.3B's full width.

    python chip_smoke.py               # one chip: three training paths + serving
    python chip_smoke.py --four-chips  # the swarm on four chips, one worker each

One chip (the default) runs, in one process, with random weights made
from a seed:

  * ``train/materialized`` — ``Trainer.from_spec`` on the ``lezo-opt13b``
    preset at OPT-1.3B (24 x 2048, vocab 50272, bf16), SST-2 prompts of
    128 tokens, batch 16, 4 LeZO steps; the ``scan`` axpy backend.
  * ``train/pallas`` — the same run with the Pallas axpy kernel; the
    compiled step must hold a Mosaic kernel and its losses must match
    ``train/materialized`` within ``PALLAS_LOSS_TOL``.
  * ``train/virtual`` — the same run with the fused virtual forward
    (``pmatmul_stack``); Mosaic kernel required, first-step loss within
    ``VIRTUAL_LOSS_TOL`` of ``train/materialized``.
  * ``serve`` — ``serving.Engine`` over the trained parameters answers 8
    greedy requests (prompts of 16-96 tokens, 32 new tokens each),
    checked against the lockstep ``launch.serve.generate`` path.

``--four-chips`` runs only the swarm (DESIGN.md §14) on OPT-1.3B with 4
loss shards through ``python -m repro.launch swarm``, first with one
worker and then with four, one chip each; the committed ``(loss,
projected_grad, seed)`` streams must be equal to the bit, every worker
must exit 0, and the four must hold four distinct chips.  This parent
process never imports JAX: a child reports what the host has, and the
swarm's own parent runs its JAX work on the host CPU.

Every phase prints one JSON line.  Its times are smoke timings — one
process, a handful of steps, compile included where labelled — not
benchmark numbers.  The last line is ``{"ok": true, "device": {...}}``;
a phase that fails raises, and the script exits non-zero before that
line.  With no TPU it exits non-zero before building any model.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit("chip_smoke.py runs from a checkout of the repository "
             f"(no src/repro beside {Path(__file__).name})")
sys.path.insert(0, str(ROOT / "src"))

from repro.launch import compile_cache  # noqa: E402

SEED = 0
STEPS = 4                 # logged training steps per phase
TIMED_STEPS = 2           # extra steps timed after the warm ones
GEN = 32                  # new tokens per served request
PROMPT_LENS = (16, 40, 72, 96, 16, 40, 72, 96)
# Losses are about 12 at this random init.  The scan and Pallas axpys
# draw the same z; they may differ in the last f32 bit (XLA may contract
# the scan's axpy into an FMA, Mosaic's transcendentals are not XLA's),
# which moves a bf16 weight only where it sits on a rounding boundary.
PALLAS_LOSS_TOL = 2e-3
# pmatmul accumulates 128-deep MXU tiles in f32 and rounds activations
# to bf16 where XLA's dot chooses its own order: a bf16 ULP here and
# there over 24 layers.
VIRTUAL_LOSS_TOL = 5e-2
# Greedy decoding over bf16 logits (8 significant bits: a ULP is 0.0625
# for logits in [8, 16)) flips on near-ties when two programs round
# differently.  Where the engine and the lockstep path diverge, both
# tokens must sit within TIE_TOL of the lockstep's top logit there.
TIE_TOL = 0.125

TRAIN_BASE = {"model.variant": "opt_1_3b", "task.name": "sst2",
              "model.seq_len": 128, "run.batch_size": 16,
              "run.steps": STEPS, "run.eval_every": 0, "run.log_every": 1,
              "run.seed": SEED}


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------- training
def train_phase(jax, name: str, overrides: dict):
    """Build the trainer from the spec, compile its step ahead of time
    (for the HLO and the compile time), train STEPS logged steps through
    ``Trainer.train``, then time TIMED_STEPS more of the same step."""
    import jax.numpy as jnp
    import numpy as np
    from repro import api, tasks
    from repro.core import rng
    from repro.train.trainer import Trainer

    spec = api.with_overrides(api.preset("lezo-opt13b"),
                              {**TRAIN_BASE, **overrides})
    trainer = Trainer.from_spec(spec)
    data = trainer.make_dataset(spec.run.batch_size)
    batch = {k: jnp.asarray(v) for k, v in data.items()
             if k in tasks.MODEL_BATCH_KEYS}
    base_seed = np.uint32(rng.fold_py(spec.run.seed, 0xC0FFEE))
    t0 = time.perf_counter()
    compiled = trainer._step.lower(trainer.trainable, trainer.est_state,
                                   batch, jnp.int32(0), base_seed).compile()
    compile_s = time.perf_counter() - t0
    mosaic = "tpu_custom_call" in compiled.as_text()
    del compiled

    hist = trainer.train()
    losses = [float(v) for v in hist["loss"]]
    params, state = hist["final_params"], trainer.est_state
    times = []
    for i in range(TIMED_STEPS):
        t0 = time.perf_counter()
        params, state, m = trainer._step(params, state, batch,
                                         jnp.int32(STEPS + i), base_seed)
        jax.block_until_ready((params, m))
        times.append(time.perf_counter() - t0)
    rec = {"phase": name, "timing": "smoke, not a benchmark",
           "compile_s": compile_s, "steady_s_per_step": min(times),
           "step_s": times, "losses": losses, "tpu_custom_call": mosaic,
           "peak_bytes_in_use": peak_bytes(jax),
           "cfg": {"layers": trainer.mcfg.num_layers,
                   "d_model": trainer.mcfg.d_model,
                   "vocab": trainer.mcfg.vocab,
                   "dtype": trainer.mcfg.dtype}}
    check(len(losses) == STEPS and all(math.isfinite(v) for v in losses),
          f"{name}: losses {losses}")
    return rec, trainer.mcfg, params


# ----------------------------------------------------------------- serving
def serve_phase(jax, cfg, params):
    import jax.numpy as jnp
    import numpy as np
    from repro import api, serving
    from repro.launch import serve as lockstep
    from repro.models import lm
    from repro.serving.scheduler import Request

    rs = np.random.default_rng(SEED)
    reqs = [Request(rid=i, tokens=rs.integers(0, cfg.vocab, n).tolist(),
                    max_new_tokens=GEN, seed=i)
            for i, n in enumerate(PROMPT_LENS)]
    eng = serving.Engine(cfg, params, api.Serving(
        page_size=16, n_pages=64, max_lanes=4, prefill_chunk=32,
        max_seq=128, max_new_tokens=GEN))

    def drain():
        n0 = eng.n_prefill_calls + eng.n_decode_steps
        t0 = time.perf_counter()
        out = {r.rid: r.tokens for r in eng.run(reqs)}
        dt = time.perf_counter() - t0
        return out, dt, eng.n_prefill_calls + eng.n_decode_steps - n0

    got, cold_s, _ = drain()
    again, warm_s, calls = drain()       # same requests, nothing compiles
    check(sorted(got) == list(range(len(reqs))), f"served {sorted(got)}")
    check(all(len(got[r.rid]) == GEN for r in reqs), "short generations")
    check(again == got, "a second drain of the same requests differs")

    match = total = 0
    diverged = []
    for r in reqs:
        ref = np.asarray(lockstep.generate(
            cfg, params, jnp.asarray([r.tokens], jnp.int32), GEN,
            max_seq=128))[0].tolist()
        eng_toks = got[r.rid]
        match += sum(a == b for a, b in zip(ref, eng_toks))
        total += GEN
        k = next((i for i, (a, b) in enumerate(zip(ref, eng_toks))
                  if a != b), None)
        if k is None:
            continue
        # where they part, the lockstep's own logits over the shared
        # context must hold both choices within TIE_TOL of the top
        ctx = jnp.asarray([list(r.tokens) + ref[:k]], jnp.int32)
        logits, _ = lm.prefill(cfg, params, ctx, max_seq=ctx.shape[1])
        lg = np.asarray(logits[0], np.float32)
        gaps = [float(lg.max() - lg[ref[k]]), float(lg.max() - lg[eng_toks[k]])]
        diverged.append({"rid": r.rid, "at": k, "lockstep": ref[k],
                         "engine": eng_toks[k], "gaps_to_top": gaps})
        check(max(gaps) <= TIE_TOL,
              f"request {r.rid} diverges at token {k} off a near-tie: "
              f"gaps to the top logit {gaps}")
    rec = {"phase": "serve", "timing": "smoke, not a benchmark",
           "requests": len(reqs), "new_tokens_each": GEN,
           "compile_s": cold_s - warm_s,
           "steady_s_per_step": warm_s / calls, "engine_calls": calls,
           "token_match_share": match / total, "bit_identical": not diverged,
           "first_divergences": diverged, "tie_tol": TIE_TOL,
           "peak_bytes_in_use": peak_bytes(jax)}
    return rec


def one_chip():
    compile_cache.enable()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                         "this smoke never runs on another platform")

    mat, cfg, params = train_phase(jax, "train/materialized", {})
    emit(mat)
    pal, _, p = train_phase(jax, "train/pallas", {"runtime.backend": "pallas"})
    del p
    pal["max_loss_diff_vs_materialized"] = max(
        abs(a - b) for a, b in zip(pal["losses"], mat["losses"]))
    pal["tol"] = PALLAS_LOSS_TOL
    emit(pal)
    check(pal["tpu_custom_call"], "train/pallas: no Mosaic kernel in the step")
    check(pal["max_loss_diff_vs_materialized"] <= PALLAS_LOSS_TOL,
          "train/pallas losses off train/materialized")
    vir, _, p = train_phase(jax, "train/virtual",
                            {"runtime.forward_backend": "virtual"})
    del p
    diffs = [abs(a - b) for a, b in zip(vir["losses"], mat["losses"])]
    vir["first_loss_diff_vs_materialized"] = diffs[0]
    vir["max_loss_diff_vs_materialized"] = max(diffs)
    vir["tol"] = VIRTUAL_LOSS_TOL
    emit(vir)
    check(vir["tpu_custom_call"], "train/virtual: no Mosaic kernel in the step")
    check(diffs[0] <= VIRTUAL_LOSS_TOL,
          "train/virtual first-step loss off train/materialized")

    emit(serve_phase(jax, cfg, params))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------- swarm
_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
          "{'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")
SWARM_STEPS = 3
SWARM_TIMEOUT_S = 240     # one swarm run: start-up, compiles and steps


def _swarm(workers: int, root: Path) -> dict:
    """One swarm run through ``python -m repro.launch swarm``, in a
    process group of its own (coordinator and workers), killed whole if
    it outlives SWARM_TIMEOUT_S.  -> the summary and committed rows."""
    sets = {**TRAIN_BASE, "run.steps": SWARM_STEPS, "swarm.n_shards": 4,
            "swarm.workers": workers,
            "swarm.step_deadline_s": float(SWARM_TIMEOUT_S),
            "telemetry.runs_dir": str(root / "runs")}
    cmd = [sys.executable, "-m", "repro.launch", "swarm",
           "--preset", "lezo-opt13b", "--no-respawn",
           "--out", str(root / "summary.json")]
    for k, v in sets.items():
        cmd += ["--set", f"{k}={v}"]
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    log = root / "swarm.log"
    t0 = time.perf_counter()
    with open(log, "w") as out:
        proc = subprocess.Popen(cmd, env=env, cwd=str(ROOT), stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=SWARM_TIMEOUT_S)
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
            else:   # workers outliving their coordinator go with it
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
    wall = time.perf_counter() - t0
    if rc != 0:
        sys.stderr.write(log.read_text()[-8000:])
        raise SystemExit(f"chip_smoke: swarm with {workers} workers "
                         f"exited {rc}")
    summary = json.loads((root / "summary.json").read_text())["summary"]
    (run_dir,) = [d for d in (root / "runs").iterdir() if d.is_dir()]
    rows = [json.loads(line) for line in open(run_dir / "steps.jsonl")]
    return summary, rows, wall


def four_chips():
    """The swarm across four chips.  This process never imports JAX: a
    child reports the host's devices, and each swarm runs in a child
    whose coordinator stays on the host CPU while each worker gets one
    chip."""
    probe = subprocess.run([sys.executable, "-c", _PROBE], check=True,
                           capture_output=True, text=True, timeout=300)
    host = json.loads(probe.stdout.strip().splitlines()[-1])
    if host["platform"] != "tpu" or host["count"] < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs a host with four "
                         f"TPU chips; JAX found {host}")
    streams = {}
    for workers in (1, 4):
        with tempfile.TemporaryDirectory() as tmp:
            summary, rows, wall = _swarm(workers, Path(tmp))
        streams[workers] = [[r["loss"], r["projected_grad"], r["seed"]]
                            for r in rows]
        devs = summary["worker_devices"]
        emit({"phase": f"swarm/workers={workers}",
              "timing": "smoke, not a benchmark (wall includes start-up "
                        "and compiles)",
              "wall_s": wall, "steps": SWARM_STEPS, "n_shards": 4,
              "stream": streams[workers],
              "worker_exits": summary["worker_exits"],
              "worker_devices": devs})
        check(len(rows) == SWARM_STEPS, f"{len(rows)} committed steps")
        check(summary["worker_exits"] == [0] * workers,
              f"worker exits {summary['worker_exits']}")
        check(len(devs) == workers and all(
            d["platform"] == "tpu" and d["n_devices"] == 1
            for d in devs.values()), f"worker devices {devs}")
        # all workers hold their chip until the run ends: the device
        # nodes they hold open name the physical chips
        chips = {(tuple(d["device_files"]), d["id"], tuple(d["coords"]))
                 for d in devs.values()}
        check(len(chips) == workers, f"workers shared a chip: {devs}")
    emit({"phase": "swarm/bit_identity",
          "identical": streams[1] == streams[4]})
    check(streams[1] == streams[4],
          "committed streams differ between 1 and 4 workers")
    return host


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the swarm phase on a four-chip host")
    args = ap.parse_args(argv)
    device = four_chips() if args.four_chips else one_chip()
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
