"""Fine-tuning cells of DeepSeek-V2-style models (MLA attention, MoE FFNs
after leading dense layers): ``drivers/train.py``'s protocol, pointed at
``reference/deepseek_v2.py``.

Set-up builds ``Trainer.from_spec`` on the cell's preset with the
configuration file turned into the program's ModelConfig here, hands
its jitted step the benchmark's weights (made on the device from the
seed), and drives that step through the first ``check_steps`` steps with
the window's own feed; the window then runs the step count one warm step
fixes, and the check runs after it, with the program's state freed: the
reference takes the same weights, rows and step seeds, and follows the
projected gradients the program reported.  ``grad_gap`` and
``change_gap`` compare each leaf's share of each step's update
(``reference.applied_steps``: the change projected on the step's z over
the step's scale, 1 where the update landed) in place of the change's
norm, which bfloat16 rounding swamps at this model's small steps: the
widest gap between the program's and the reference's share, in
updates, after the first step and, over every step, after the last.  Every stacked leaf is judged on every step that
updated it; the unstacked ones only where the update is at least an
ulp of their weights (below it what is stored depends on how often the
compiler rounds).  A run in which no bfloat16 stacked leaf was judged
reads 1 there, as a state left unchanged does.  The control adds, beside the
float8 and half-batch readings, the reference with the routed experts'
update left out (``expert_update_left_out``).  The trainer is built on the
parameters' shapes alone (the weights it would make are dropped for the
benchmark's, and a stage of this size fits the chip once, not twice).

With ``--trace 1`` the raw trace is kept and each of the MoE FFN's
scopes (``moe_dispatch``, ``moe_experts``, ``moe_combine``) and of the
parameter sweeps' stages (``perturb``, ``update_axpy``) gets its
operations' own device time in the window.  The grouped matmuls are
custom calls that XLA names ``ragged-dot-*`` and whose scope path it
drops, so those count under ``moe_experts`` by name.
"""
from __future__ import annotations

import gc
import importlib
import os
import re
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict
from unittest import mock

import numpy as np

import harness
import stages
from reference import deepseek_v2 as ref
from reference import rng as ref_rng
from traffic import generate

VARIANT = "chipbench_config"
ARCH = "deepseek-v2-lite-16b"
MOE_SCOPES = ("moe_dispatch", "moe_experts", "moe_combine")
SWEEPS = ("perturb", "update_axpy")
EXPERT_LEAVES = ("ffn/we_g", "ffn/we_u", "ffn/we_d")
GROUPED_MATMUL = re.compile(r"%?ragged-dot")


def model_config(cfg: Dict):
    """The program's ModelConfig for a DeepSeek-V2 configuration file."""
    from repro.models.config import BlockCfg, ModelConfig, StageCfg, YarnCfg
    rs = cfg["rope_scaling"]
    plain = {"model_type": "deepseek_v2", "q_lora_rank": None,
             "hidden_act": "silu", "attention_bias": False,
             "scoring_func": "softmax", "topk_method": "greedy",
             "routed_scaling_factor": 1, "moe_layer_freq": 1, "n_group": 1,
             "topk_group": 1}
    odd = {k: cfg.get(k) for k, v in plain.items() if cfg.get(k) != v}
    if (odd or rs.get("type") != "yarn"
            or cfg["v_head_dim"] != cfg["qk_nope_head_dim"]
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or cfg["assumed"]["rope_pairing"] != "halves"):
        raise SystemExit(f"chipbench: no mapping for this configuration "
                         f"({odd or 'rope, head or pairing layout'})")
    n_dense, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    H = cfg["num_attention_heads"]
    return ModelConfig(
        name=f"chipbench-{cfg['hidden_size']}x{L}", d_model=cfg["hidden_size"],
        n_heads=H, n_kv_heads=H, d_head=cfg["qk_nope_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        stages=(StageCfg(n_dense, (BlockCfg("mla", "dense",
                                            d_ff=cfg["intermediate_size"]),)),
                StageCfg(L - n_dense, (BlockCfg("mla", "moe"),))),
        kv_lora=cfg["kv_lora_rank"], rope_head_dim=cfg["qk_rope_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnCfg(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=float(cfg["rms_norm_eps"]),
        n_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        max_seq=cfg["max_position_embeddings"], dtype=cfg["assumed"]["dtype"])


def judged(cfg: Dict, probes, change1, change_last, r) -> Dict[str, float]:
    """The mean gap of the probe losses, and the widest gap between the
    program's and the reference's share of a step's update over every
    judged key after the first step and after the last (the two judge
    the same keys).  The share is in updates: where most of a leaf's
    change is rounding, both hold the same bits and the shares differ by
    the update alone where it was left out.  A number whose keys hold no
    stacked leaf of the weights' type (bfloat16 in the cell) reads 1:
    nothing shows that the update landed."""
    dts = {p: dt for p, _, dt, *_ in ref.layout(cfg)}
    weights = {p for p, dt in dts.items()
               if p.startswith("stages/") and dt == dts["embed/tok"]}

    def gap(prog, refn):
        if not any(k.split("@")[0] in weights for k in refn):
            return 1.0
        return max(abs(prog[k] - refn[k]) for k in refn)
    return {"loss_gap_mean": statistics.fmean(
                abs(a - b) for a, b in zip(probes, r["probes"])),
            "grad_gap": gap(change1, r["change1"]),
            "change_gap": gap(change_last, r["change_last"])}


def check_layout(cfg: Dict, program_tree) -> None:
    got = {p: (tuple(a.shape), str(a.dtype))
           for p, a in ref.from_tree(program_tree).items()}
    want = {p: (tuple(s), dt) for p, s, dt, *_ in ref.layout(cfg)}
    if got != want:
        raise SystemExit(f"chipbench: parameter layout differs: program "
                         f"{sorted(set(got.items()) - set(want.items()))} "
                         f"benchmark {sorted(set(want.items()) - set(got.items()))}")


def scope_of(tf_op: str, name: str) -> str:
    """The innermost MoE scope on an operation's path, cut where the
    path's root repeats (as ``stages.stage_of``); the grouped matmuls by
    their name; else the parameter sweeps' stage (``perturb`` or
    ``update_axpy``) the operation runs in; '' for any other operation."""
    if GROUPED_MATMUL.match(name):
        return "moe_experts"
    parts = re.split(r"[/;:]", tf_op or "")
    if parts[0] in parts[1:]:
        parts = parts[:parts.index(parts[0], 1)]
    moe = next((p for p in reversed(parts) if p in MOE_SCOPES), "")
    stage = stages.stage_of(tf_op)
    return moe or (stage if stage in SWEEPS else "")


def scope_s(path: str) -> Dict[str, float]:
    """Own device seconds per MoE scope and per sweep stage in the
    trace's window, averaged over the devices."""
    data = stages.load(path)
    out = defaultdict(float)
    for op, own in stages._own_in_window(data):
        s = scope_of(op.get("tf_op", ""), op["name"])
        if s:
            out[s] += own / 1e9 / len(data["devices"])
    return dict(out)


def run(ctx: "harness.Run") -> "harness.Result":
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.models import lm
    from repro.train.trainer import Trainer

    cfg, mix, wl = ctx.cell.cfg, ctx.cell.mix, ctx.cell.workload
    B, S, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    setattr(importlib.import_module("repro.configs.deepseek_v2_lite"),
            VARIANT, lambda: model_config(cfg))
    run_seed = ctx.seed & 0x7FFFFFFF
    spec = api.with_overrides(api.preset(wl["preset"]), {
        **wl.get("spec", {}), "model.arch": ARCH, "model.variant": VARIANT,
        "model.seq_len": S, "run.batch_size": B, "run.seed": run_seed,
        "run.eval_every": 0})
    init = lm.init_params
    with mock.patch.object(lm, "init_params",
                           lambda c, k: jax.eval_shape(lambda: init(c, k))):
        trainer = Trainer.from_spec(spec)
    check_layout(cfg, trainer.trainable)
    trainer.trainable = trainer.base_params = None
    params = ref.to_tree(ref.make_weights(cfg, ctx.seed))
    step, state = trainer._step, trainer.est_state
    base_seed = np.uint32(ref_rng.fold_py(run_seed, 0xC0FFEE))
    log_every = spec.run.log_every
    o = spec.optimizer
    n_drop = (o.n_drop if o.n_drop is not None
              else int(o.sparsity * cfg["num_hidden_layers"]))

    def feed(t):
        with ctx.span("next_batch"):
            rows = generate.rows(mix, V, ctx.seed, t)
            return rows, {k: jnp.asarray(v) for k, v in rows.items()}

    def one(t, batch):
        with ctx.span("step"):
            return step(params, state, batch, jnp.int32(t), base_seed)

    # -- set-up: the first steps through the window's own call and feed
    n_check = wl["check_steps"]
    check_rows, probes, grads, prog, steps = [], [], [], {}, []
    t_warm = None
    for t in range(n_check):
        rows, batch = feed(t)
        check_rows.append(rows)
        if t == n_check - 1:
            jax.block_until_ready(params)
            t0 = time.perf_counter()
        params, state, m = one(t, batch)
        lv, g = np.float32(m["loss"]), np.float32(m["projected_grad"])
        eps = np.float32(spec.optimizer.eps)
        probes += [float(lv + eps * g), float(lv - eps * g)]
        grads.append(float(g))
        if t == n_check - 1:
            t_warm = time.perf_counter() - t0
        # the reference's rows and z: a step that updated other rows, or
        # along another direction, reads 0 there
        step_seed = ref_rng.fold_py(int(base_seed), t)
        steps.append((step_seed, ref.select(cfg, step_seed, t, n_drop),
                      ref.intended(o.lr, g)))
        if t == 0 or t == n_check - 1:
            with ctx.bookkeeping():
                prog["change1" if t == 0 else "change_last"] = \
                    ref.applied_steps(cfg, ctx.seed, ref.from_tree(params),
                                      steps)
    n_window = max(1, round(ctx.seconds / t_warm))
    setup_s = ctx.setup_s(time.perf_counter())

    # -- the window
    tmp = None
    if ctx.trace and not ctx.keep_trace:
        fd, tmp = tempfile.mkstemp(suffix=".xplane.pb")
        os.close(fd)
        ctx.keep_trace = tmp
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
    scopes = scope_s(ctx.keep_trace) if ctx.trace else {}
    if tmp:
        os.remove(tmp)

    # -- the check, with the program's state gone
    del params, state, m, step, trainer
    gc.collect()

    def reference(run_grads):
        return ref.zo_readings(cfg, ctx.seed, check_rows, int(base_seed),
                               eps=o.eps, lr=o.lr, n_drop=n_drop, mm="f32",
                               grads=run_grads)

    def show(name, run_probes, run_last, r):
        print(f"{name} probe losses (run, reference): " + " ".join(
            f"{a!r},{b!r}" for a, b in zip(run_probes, r["probes"])),
            file=sys.stderr, flush=True)
        wide = sorted(run_last, key=lambda k: -abs(run_last[k]
                                                   - r["change_last"][k]))
        print(f"{name} widest shares (key run reference): " + " ".join(
            f"{k} {run_last[k]!r} {r['change_last'][k]!r}" for k in wide[:3]),
            file=sys.stderr, flush=True)

    r = reference(grads)
    checks = ctx.checks(judged(cfg, probes, prog["change1"],
                               prog["change_last"], r))
    show("program", probes, prog["change_last"], r)
    if ctx.control:
        for name, kw in (("fp8", {"mm": "fp8"}),
                         ("half_batch", {"mm": "f32", "half_batch": True}),
                         ("expert_update_left_out",
                          {"mm": "f32", "skip_update": EXPERT_LEAVES})):
            c = ref.zo_readings(cfg, ctx.seed, check_rows, int(base_seed),
                                eps=o.eps, lr=o.lr, n_drop=n_drop, **kw)
            rc = reference(c["grad"])
            ctx.extra[name] = judged(cfg, c["probes"], c["change1"],
                                     c["change_last"], rc)
            show(name, c["probes"], c["change_last"], rc)
    e2e = {"train_tokens_per_s": n_window * B * S / window_s,
           "setup_s": setup_s}
    readings = {"counts": counts, "cfg": cfg, "peak": ctx.peak,
                "chips": ctx.cell.chips, "trace": ctx.trace_summary,
                "n_drop": n_drop, "scope_s": scopes}
    return harness.Result(end_to_end=e2e, checks=checks, attempted=n_window,
                          failed=0, memory_peak_bytes=peak,
                          window_compiles=compiles, readings=readings)
