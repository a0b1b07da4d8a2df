"""Fine-tuning cells: the spec-built trainer's jitted ZO step, fed fresh
rows every step as ``Trainer.train`` feeds it.

Set-up builds ``Trainer.from_spec`` on the cell's preset at the cell's
configuration, hands its step the benchmark's weights (made on the
device from the seed), and drives that same step through the first
``check_steps`` steps with the window's own feed.  Those steps compile
the step, give the check its readings (each step's loss; each leaf's
change after the first step and after the last) and time one warm step,
from which the window's step count is fixed.  The window then runs that
many steps, the feed running throughout and the loss read back every
``run.log_every`` steps, and ends on ``block_until_ready``.

The check runs after the window, once the program's state is freed:
the reference (``reference/opt.py``) makes the same weights, takes the
same rows and step seeds, and runs the same ZO steps in float32, each
update applying the projected gradient the program reported, so that
every step's losses are read at the program's own weights.
"""
from __future__ import annotations

import gc
import importlib
import statistics
import sys
import time
from typing import Dict

import numpy as np

import harness
from reference import opt as ref
from reference import rng as ref_rng
from traffic import generate

VARIANT = "chipbench_config"


def check_layout(cfg: Dict, program_tree) -> None:
    """The program's parameter tree must be the layout the benchmark's
    weights and reference use, leaf for leaf."""
    got = {p: (tuple(a.shape), str(a.dtype))
           for p, a in ref.from_tree(program_tree).items()}
    want = {p: (tuple(s), dt) for p, s, dt, *_ in ref.layout(cfg)}
    if got != want:
        raise SystemExit(f"chipbench: parameter layout differs: program "
                         f"{sorted(set(got.items()) - set(want.items()))} "
                         f"benchmark {sorted(set(want.items()) - set(got.items()))}")


def worst_leaf(prog: Dict[str, float], refn: Dict[str, float], keep) -> float:
    """The widest gap between the program's and the reference's norm of
    a leaf, as a share of the reference's norm of that leaf or of the
    median leaf's, whichever is larger."""
    med = statistics.median(refn.values())
    return max(abs(prog[k] - refn[k]) / max(refn[k], med) for k in keep)


def gaps(probes, change1, change_last, r) -> Dict[str, float]:
    """The numbers compared against the reference's readings ``r``,
    taken on the trajectory of the run being judged (``zo_readings``
    with its projected gradients).  The losses are compared by the mean
    gap over every probe of every step: the widest of them is the tail
    of one probe's rounding and does not part bfloat16 from float8
    (PERF.md).  Leaves whose first change in the reference is under a
    thousandth of the median leaf's move by round-off alone and are
    left out."""
    med = statistics.median(r["change1"].values())
    keep = [k for k, v in r["change1"].items() if v >= 1e-3 * med]
    return {"loss_gap_mean": statistics.fmean(
                abs(a - b) for a, b in zip(probes, r["probes"])),
            "grad_gap": worst_leaf(change1, r["change1"], keep),
            "change_gap": worst_leaf(change_last, r["change_last"], keep)}


def run(ctx: "harness.Run") -> "harness.Result":
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.train.trainer import Trainer

    cfg, mix, wl = ctx.cell.cfg, ctx.cell.mix, ctx.cell.workload
    B, S, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    # the configuration, as a variant the spec's model registry resolves
    setattr(importlib.import_module("repro.configs.opt"), VARIANT,
            lambda: harness.model_config(cfg))
    run_seed = ctx.seed & 0x7FFFFFFF
    spec = api.with_overrides(api.preset(wl["preset"]), {
        **wl.get("spec", {}), "model.arch": "opt-13b",
        "model.variant": VARIANT, "model.seq_len": S, "run.batch_size": B,
        "run.seed": run_seed, "run.eval_every": 0})
    trainer = Trainer.from_spec(spec)
    check_layout(cfg, trainer.trainable)
    trainer.trainable = trainer.base_params = None    # the benchmark's own
    params = ref.to_tree(ref.make_weights(cfg, ctx.seed))
    step, state = trainer._step, trainer.est_state
    # Trainer.train's seeding: one base seed, folded with the step index
    base_seed = np.uint32(ref_rng.fold_py(run_seed, 0xC0FFEE))
    log_every = spec.run.log_every

    def feed(t):
        with ctx.span("next_batch"):
            rows = generate.rows(mix, V, ctx.seed, t)
            return rows, {k: jnp.asarray(v) for k, v in rows.items()}

    def one(t, batch):
        with ctx.span("step"):
            return step(params, state, batch, jnp.int32(t), base_seed)

    # -- set-up: the first steps through the window's own call and feed
    n_check = wl["check_steps"]
    check_rows, probes, grads, prog = [], [], [], {}
    t_warm = None
    for t in range(n_check):
        rows, batch = feed(t)
        check_rows.append(rows)
        if t == n_check - 1:
            jax.block_until_ready(params)
            t0 = time.perf_counter()
        params, state, m = one(t, batch)
        # the two probe losses, back from their mean and difference
        lv, g = np.float32(m["loss"]), np.float32(m["projected_grad"])
        eps = np.float32(spec.optimizer.eps)
        probes += [float(lv + eps * g), float(lv - eps * g)]
        grads.append(float(g))
        if t == n_check - 1:
            t_warm = time.perf_counter() - t0
        if t == 0 or t == n_check - 1:
            with ctx.bookkeeping():
                prog["change1" if t == 0 else "change_last"] = \
                    ref.change_norms(cfg, ctx.seed, ref.from_tree(params))
    n_window = max(1, round(ctx.seconds / t_warm))
    setup_s = ctx.setup_s(time.perf_counter())

    # -- the window
    compiles0 = ctx.watch.n
    counts = {"steps": n_window, "batch": B, "seq_len": S}
    head_positions = 0
    with ctx.window():
        t0 = time.perf_counter()
        for i in range(n_window):
            t = n_check + i
            rows, batch = feed(t)
            head_positions += int(rows["loss_mask"].sum())
            params, state, m = one(t, batch)
            if log_every and t % log_every == 0:
                with ctx.span("log_sync"):
                    float(m["loss"])
        jax.block_until_ready(params)
        window_s = time.perf_counter() - t0
    compiles = ctx.watch.n - compiles0
    peak = harness.memory_peak_bytes()
    counts["head_positions"] = head_positions
    counts["window_s"] = window_s

    # -- the check, with the program's state gone
    del params, state, m, step, trainer
    gc.collect()
    o = spec.optimizer
    n_drop = (o.n_drop if o.n_drop is not None
              else int(o.sparsity * cfg["num_hidden_layers"]))
    # the reference follows the program's steps: a later step's losses
    # are read at the program's weights, not at weights the bfloat16
    # rounding of an earlier l+ - l- has sent elsewhere (PERF.md)
    def reference(run_grads):
        return ref.zo_readings(cfg, ctx.seed, check_rows, int(base_seed),
                               eps=o.eps, lr=o.lr, n_drop=n_drop, mm="f32",
                               grads=run_grads)

    def show(name, run_probes, r):
        print(f"{name} probe losses (run, reference): " + " ".join(
            f"{a!r},{b!r}" for a, b in zip(run_probes, r["probes"])),
            file=sys.stderr, flush=True)

    r = reference(grads)
    checks = ctx.checks(gaps(probes, prog["change1"], prog["change_last"], r))
    show("program", probes, r)
    if ctx.control:
        # the reference in the program's place: in fp8, and on half the batch
        for name, kw in (("fp8", {"mm": "fp8"}),
                         ("half_batch", {"mm": "f32", "half_batch": True})):
            c = ref.zo_readings(cfg, ctx.seed, check_rows, int(base_seed),
                                eps=o.eps, lr=o.lr, n_drop=n_drop, **kw)
            rc = reference(c["grad"])
            ctx.extra[name] = gaps(c["probes"], c["change1"],
                                   c["change_last"], rc)
            show(name, c["probes"], rc)
    e2e = {"train_tokens_per_s": n_window * B * S / window_s,
           "setup_s": setup_s}
    readings = {"counts": counts, "cfg": cfg, "peak": ctx.peak,
                "chips": ctx.cell.chips, "trace": ctx.trace_summary,
                "n_drop": n_drop}
    return harness.Result(end_to_end=e2e, checks=checks, attempted=n_window,
                          failed=0, memory_peak_bytes=peak,
                          window_compiles=compiles, readings=readings)

