"""Spec consumers: derive() legacy configs, and the top-level entrypoints
``run`` / ``evaluate`` / ``dryrun`` / ``sweep``.

``derive`` materializes today's ``ZOConfig`` / ``EstimatorConfig`` /
``TrainConfig`` / ``FOConfig`` / ``LoRAConfig`` / ``PrefixConfig`` from
the spec, so ``Trainer``, ``estimators.make_step`` and the fused runtime
stay bit-identical underneath — the equivalence suite in
tests/test_api.py holds that line for every estimator x forward backend.

This module is imported lazily by ``repro.api`` (it pulls jax via the
trainer); spec/validate/presets stay import-light for the CLI.

Part of the unified experiment-spec surface (DESIGN.md §11).
"""
import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional

from repro import estimators
from repro import tasks as tasks_mod
from repro.api import presets as presets_mod
from repro.api.spec import Experiment, SpecError, to_dict, with_overrides
from repro.api.validate import n_drop_for, resolve_model
from repro.api.validate import validate as validate_spec
from repro.core import fo, zo
from repro.data import synthetic
from repro.peft import lora as lora_mod
from repro.peft import prefix as prefix_mod


class Derived(NamedTuple):
    """The legacy config tree a spec materializes to."""
    model_cfg: Any
    task: Any                     # synthetic.TaskConfig | tasks.CompiledTask
    tcfg: Any                     # train.trainer.TrainConfig
    zo_cfg: zo.ZOConfig
    fo_cfg: fo.FOConfig
    est_cfg: estimators.EstimatorConfig
    lora_cfg: lora_mod.LoRAConfig
    prefix_cfg: prefix_mod.PrefixConfig
    n_drop: int


def derive(spec: Experiment) -> Derived:
    """Validate ``spec`` and materialize the legacy configs it implies."""
    from repro.train.trainer import TrainConfig  # trainer imports repro.api

    mcfg = validate_spec(spec)
    m, t, o, e, rt, r = (spec.model, spec.task, spec.optimizer,
                         spec.estimator, spec.runtime, spec.run)
    if t.name is not None:
        task = tasks_mod.build(t.name, vocab=mcfg.vocab, seq_len=m.seq_len,
                               seed=r.seed)
    else:
        task = synthetic.TaskConfig(vocab=mcfg.vocab, seq_len=m.seq_len,
                                    n_classes=t.n_classes,
                                    signal_rate=t.signal_rate, seed=r.seed)
    n_drop = n_drop_for(spec, mcfg.num_layers)
    eval_every = (max(1, r.steps // 4) if r.eval_every is None
                  else r.eval_every)
    tcfg = TrainConfig(
        steps=r.steps, batch_size=r.batch_size, eval_every=eval_every,
        log_every=r.log_every, seed=r.seed, mode=o.mode,
        estimator=e.name, est_q=e.q,
        ckpt_dir=r.ckpt_dir, ckpt_every=r.ckpt_every,
        keep_ckpts=r.keep_ckpts,
        n_loss_shards=rt.n_loss_shards, quorum=rt.quorum,
        peft=rt.peft, forward_backend=rt.forward_backend)
    zo_cfg = zo.ZOConfig(
        eps=o.eps, lr=o.lr, n_drop=n_drop, policy=o.policy,
        backend=rt.backend, fused_update=o.fused_update,
        weight_decay=o.weight_decay,
        forward_backend=rt.forward_backend,
        paired_probes=rt.paired_probes)
    est_cfg = estimators.from_zo(zo_cfg, name=e.name, q=e.q,
                                 q_chunk=e.q_chunk, inner=e.inner,
                                 importance_decay=e.importance_decay)
    fo_cfg = fo.FOConfig(optimizer=o.fo_optimizer, lr=o.lr,
                         weight_decay=o.weight_decay, grad_clip=o.grad_clip)
    lora_cfg = lora_mod.LoRAConfig(rank=rt.lora_rank, alpha=rt.lora_alpha,
                                   targets=tuple(rt.lora_targets))
    prefix_cfg = prefix_mod.PrefixConfig(n_prefix=rt.prefix_tokens)
    return Derived(mcfg, task, tcfg, zo_cfg, fo_cfg, est_cfg, lora_cfg,
                   prefix_cfg, n_drop)


def preset(name: str) -> Experiment:
    return presets_mod.get(name)


def _summary(spec: Experiment, d: Derived, hist: Dict) -> Dict:
    return {
        "arch": spec.model.arch,
        "mode": spec.optimizer.mode,
        "estimator": spec.estimator.name, "q": spec.estimator.q,
        "forward_backend": spec.runtime.forward_backend,
        "task": spec.task.name or "synthetic",
        "metric": hist.get("metric_name", "val_loss"),
        "n_layers": d.model_cfg.num_layers, "n_drop": d.n_drop,
        "final_loss": hist["loss"][-1] if hist["loss"] else None,
        "val_loss": hist["val_loss"], "val_acc": hist["val_acc"],
        "best_step": hist.get("best_step"),
        "run_id": hist.get("run_id"), "run_dir": hist.get("run_dir"),
    }


def run(spec: Experiment, train_data=None, val_data=None) -> Dict:
    """Train per the spec.  Returns ``{"spec", "summary", "history"}`` —
    the spec dict is embedded so every result artifact is replayable."""
    from repro.train.trainer import Trainer

    trainer = Trainer.from_spec(spec)
    hist = trainer.train(train_data=train_data, val_data=val_data)
    d = trainer.derived
    return {"spec": to_dict(spec), "summary": _summary(spec, d, hist),
            "history": hist}


def evaluate(spec: Experiment, mode: str = "zeroshot",
             n_examples: int = 256) -> Dict:
    """One task's metric report (the SuperGLUE protocol; DESIGN.md §9).

    ``mode="zeroshot"`` scores fresh params (or, when ``run.ckpt_dir``
    is set, the latest checkpoint there); ``mode="train"`` fine-tunes
    first and reports both numbers.
    """
    from repro.train.trainer import Trainer

    if spec.task.name is None:
        raise SpecError("task.name", "evaluate requires a registry task")
    if mode not in ("zeroshot", "train"):
        raise SpecError("<mode>", f"unknown evaluate mode {mode!r}")
    ckpt_dir = spec.run.ckpt_dir
    if ckpt_dir is not None and mode == "train":
        # Trainer auto-resumes from ckpt_dir, which would silently turn
        # "fine-tune then score" into "restore then maybe-train"
        raise SpecError("run.ckpt_dir", "scores an existing checkpoint; "
                        "combine it with mode=zeroshot, not train")
    trainer = Trainer.from_spec(spec)
    task = trainer.registry_task
    val = trainer.make_dataset(n_examples, seed_shift=1)
    report = {"task": task.name, "kind": task.kind, "metric": task.metric,
              "arch": spec.model.arch, "variant": spec.model.variant,
              "n_examples": n_examples, "mode": mode,
              "spec": to_dict(spec)}
    zs_loss, zs_metric = trainer.evaluate(trainer.trainable, val,
                                          max_examples=n_examples)
    report["zeroshot"] = zs_metric
    report["zeroshot_val_loss"] = zs_loss
    if ckpt_dir is not None and mode != "train":
        params, step, _, _ = trainer.ckpt.restore(trainer.trainable)
        vl, metric = trainer.evaluate(params, val, max_examples=n_examples)
        report.update(trained=metric, trained_val_loss=vl, ckpt_step=step)
    elif mode == "train":
        hist = trainer.train(val_data=val)
        params = hist.get("best_params", hist["final_params"])
        vl, metric = trainer.evaluate(params, val, max_examples=n_examples)
        report.update(trained=metric, trained_val_loss=vl,
                      best_step=hist.get("best_step", -1),
                      val_metric_curve=hist["val_acc"])
    return report


def dryrun_cell(spec: Experiment, shape: str, arch: Optional[str] = None,
                multi_pod: Optional[bool] = None,
                lowering: str = "optimized", save_hlo: Optional[str] = None,
                overrides: Optional[Dict] = None) -> Dict:
    """One lower+compile roofline cell.  The single implementation both
    ``api.dryrun`` and the CLI grid loop share: the record embeds the
    spec *of the cell* (arch/mesh substituted when the grid varies
    them), so every artifact stays replayable."""
    from repro.launch import dryrun as dryrun_mod

    arch = spec.model.arch if arch is None else arch
    mp = (spec.runtime.mesh == "multi_pod") if multi_pod is None \
        else multi_pod
    rec = dryrun_mod.run_cell(
        arch, shape, mp, lowering, hlo_dir=save_hlo, overrides=overrides,
        estimator=spec.estimator.name, q=spec.estimator.q,
        forward_backend=spec.runtime.forward_backend)
    rec["spec"] = to_dict(with_overrides(spec, {
        "model.arch": arch,
        "runtime.mesh": "multi_pod" if mp else "single"}))
    return rec


def dryrun(spec: Experiment, shape: Optional[str] = None,
           lowering: str = "optimized", save_hlo: Optional[str] = None,
           overrides: Optional[Dict] = None) -> List[Dict]:
    """Lower + compile the spec's arch on the production mesh and
    return the roofline records (one per shape cell).

    Must run before jax initializes real devices — the dry-run pins
    ``xla_force_host_platform_device_count`` at import (the unified CLI
    and the legacy ``launch.dryrun`` entrypoint both guarantee this).
    """
    validate_spec(spec)
    from repro.configs.shapes import SHAPES, shapes_for

    mcfg = resolve_model(
        with_overrides(spec, {"model.variant": "full"}))
    shapes = [SHAPES[shape]] if shape else shapes_for(mcfg)
    return [dryrun_cell(spec, sh.name, lowering=lowering,
                        save_hlo=save_hlo, overrides=overrides)
            for sh in shapes]


def sweep(spec: Experiment, overrides: List[Dict[str, Any]],
          train_data=None, val_data=None) -> List[Dict]:
    """Run ``spec`` once per override set (dotted-path dicts), returning
    ``[{"overrides", "result"}, ...]`` — every scenario is a spec diff."""
    out = []
    for ov in overrides:
        varied = with_overrides(spec, dict(ov))
        out.append({"overrides": dict(ov),
                    "result": run(varied, train_data=train_data,
                                  val_data=val_data)})
    return out
