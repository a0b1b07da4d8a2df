"""Training driver: ZO (MeZO/LeZO) and FO (the paper's FT baseline).

Handles: jit + buffer donation, eval/validation cadence, best-checkpoint
selection on validation loss (the paper's protocol), resume-from-latest,
and the loss-quorum straggler simulation (DESIGN.md §7): the global batch
is split into ``n_loss_shards`` (stand-ins for data-parallel replica
groups) and each SPSA forward averages only the shards that "arrived" —
a deterministic per-step subset when ``quorum < 1``.  SPSA only needs *a*
mini-batch loss, so stragglers cost variance, not correctness; the test
suite checks convergence still holds at quorum=0.75.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import estimators
from repro import obs as obs_mod
from repro import tasks as tasks_mod
from repro.core import fo, rng, zo, zo_adaptive
from repro.data import synthetic
from repro.models import frontends, lm
from repro.peft import lora as lora_mod
from repro.peft import prefix as prefix_mod
from repro.checkpoint.manager import CheckpointManager


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 500
    batch_size: int = 16
    eval_every: int = 100
    log_every: int = 50
    seed: int = 0
    mode: str = "zo"              # zo | zo_momentum | fo
    # gradient estimator for mode="zo" (see repro.estimators):
    # two_point | one_sided | averaged | importance
    estimator: str = "two_point"
    est_q: int = 1                # directions/step for one_sided & averaged
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    keep_ckpts: int = 2
    # straggler simulation
    n_loss_shards: int = 1
    quorum: float = 1.0
    # peft
    peft: Optional[str] = None    # None | lora | prefix
    # materialized | virtual | virtual_ref — virtual runs probe forwards
    # against in-kernel-regenerated perturbed weights (repro.fused):
    # a ZO step writes parameters exactly once (the update axpy)
    forward_backend: str = "materialized"


class Trainer:
    """``task`` is either a legacy ``synthetic.TaskConfig`` or a registry
    ``tasks.CompiledTask``.  Registry tasks switch validation to the
    task's metric protocol and best-checkpoint selection to highest
    metric (the SuperGLUE protocol); synthetic tasks keep lowest
    validation loss, the paper's protocol.

    Preferred construction is :meth:`from_spec` on a ``repro.api``
    :class:`Experiment` — the legacy direct construction keeps working
    bit-identically but soft-warns (DESIGN.md §11).
    """

    @classmethod
    def from_spec(cls, spec) -> "Trainer":
        """Build from a validated ``repro.api.Experiment``.  The derived
        legacy configs are exactly what the old hand-wired construction
        produced, so the step stream is bit-identical; the spec rides
        along into every checkpoint manifest this trainer writes."""
        from repro import api
        d = api.derive(spec)
        return cls(d.model_cfg, d.task, d.tcfg, zo_cfg=d.zo_cfg,
                   fo_cfg=d.fo_cfg, lora_cfg=d.lora_cfg,
                   prefix_cfg=d.prefix_cfg, est_cfg=d.est_cfg,
                   _spec=spec, _derived=d)

    def __init__(self, model_cfg, task,
                 tcfg: TrainConfig,
                 zo_cfg: zo.ZOConfig = zo.ZOConfig(),
                 fo_cfg: fo.FOConfig = fo.FOConfig(),
                 lora_cfg: lora_mod.LoRAConfig = lora_mod.LoRAConfig(),
                 prefix_cfg: prefix_mod.PrefixConfig = prefix_mod.PrefixConfig(),
                 est_cfg: Optional[estimators.EstimatorConfig] = None,
                 _spec=None, _derived=None):
        if _spec is None:
            warnings.warn(
                "legacy Trainer(model_cfg, task, tcfg, ...) construction; "
                "prefer Trainer.from_spec(repro.api.Experiment(...)) — the "
                "spec validates every config combination at build time and "
                "rides along into checkpoints (DESIGN.md §11)",
                DeprecationWarning, stacklevel=2)
        self.experiment = _spec
        self.derived = _derived
        # optimizer-health run log (DESIGN.md §13): telemetry.runs_dir
        # makes every train() write <runs_dir>/<run_id>/ (spec + per-step
        # scalar stream + summary) — the substrate of `launch report` and
        # the bit-identity verifier `launch replay`
        tel = getattr(_spec, "telemetry", None)
        self.runlog = None
        self.health = None
        self.run_id = None
        if tel is not None and tel.runs_dir:
            from repro import api
            self.run_id = tel.run_id or obs_mod.make_run_id(
                tel.runs_dir, seed=tcfg.seed)
            self.runlog = obs_mod.RunLog(tel.runs_dir, self.run_id,
                                         spec=api.to_dict(_spec))
            if tel.enabled and not tel.jsonl:
                # no explicit span sink: the PR 6 stage trace joins the
                # run dir, so `launch report` can merge stage timings
                tel = dataclasses.replace(tel, jsonl=self.runlog.trace_path)
        # telemetry: NULL_SESSION unless the spec's telemetry node asked
        # for it — drivers hold a Session unconditionally (DESIGN.md §13)
        self.obs = obs_mod.session(tel)
        self.mcfg, self.task, self.tcfg = model_cfg, task, tcfg
        if tcfg.forward_backend != "materialized":
            zo_cfg = dataclasses.replace(zo_cfg,
                                         forward_backend=tcfg.forward_backend)
        self.zo_cfg, self.fo_cfg = zo_cfg, fo_cfg
        self.registry_task = (task if isinstance(task, tasks_mod.CompiledTask)
                              else None)
        # explicit est_cfg wins; else lift zo_cfg + TrainConfig plumbing
        self.est_cfg = est_cfg or estimators.from_zo(
            zo_cfg, name=tcfg.estimator, q=tcfg.est_q)
        if self.est_cfg.forward_backend != "materialized":
            from repro.api.validate import virtual_block_errors
            if tcfg.peft:
                raise ValueError("forward_backend='virtual' covers "
                                 "full-parameter ZO only (no PEFT merge)")
            if tcfg.mode != "zo":
                raise ValueError("forward_backend='virtual' requires "
                                 "mode='zo'")
            bad = virtual_block_errors(model_cfg)
            if bad:
                raise ValueError(
                    "forward_backend='virtual' covers attn + dense blocks; "
                    f"model has {bad}")
        key = jax.random.PRNGKey(tcfg.seed)
        self.base_params = lm.init_params(model_cfg, key)

        # trainable tree + loss over it
        if tcfg.peft == "lora":
            self.trainable = lora_mod.init_lora(self.base_params, lora_cfg,
                                                jax.random.fold_in(key, 1))
            group_fn = lora_mod.lora_group_fn
            self._to_model = lambda tr: lora_mod.merge(self.base_params, tr,
                                                       lora_cfg)
        elif tcfg.peft == "prefix":
            self.trainable = prefix_mod.init_prefix(model_cfg,
                                                    jax.random.fold_in(key, 2),
                                                    prefix_cfg)
            group_fn = prefix_mod.prefix_group_fn
            self._to_model = lambda tr: prefix_mod.inject(self.base_params, tr)
        else:
            self.trainable = self.base_params
            group_fn = lm.zo_group_fn
            self._to_model = lambda tr: tr

        self.spec = zo.build_spec(self.trainable, group_fn)
        self._build_loss()
        self._build_step()
        if self.runlog is not None:
            norm_fn = None
            if getattr(_spec.telemetry, "health_norms", False) \
                    and tcfg.mode == "zo" and self.spec.num_layers:
                norm_fn = self._make_norm_fn()
            self.health = obs_mod.HealthAccumulator(self.spec.num_layers,
                                                    norm_fn=norm_fn)
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep_ckpts)
                     if tcfg.ckpt_dir else None)

    def _make_norm_fn(self):
        """Exact ‖z(seed)‖ on the recorded layer selection — evaluated at
        drain time (off the hot path), jitted once per mask dtype."""
        spec = self.spec
        shapes = zo.leaf_shapes(self.trainable)

        @jax.jit
        def znorm(seed, gmask):
            return zo.tree_z_norm(spec, shapes, seed, spec.split_mask(gmask))

        def norm_fn(seed, layer_sel):
            gmask = jnp.asarray(np.asarray(layer_sel) > 0)
            return float(znorm(jnp.uint32(seed), gmask))

        return norm_fn

    # ------------------------------------------------------------- loss
    def _build_loss(self):
        mcfg, tcfg = self.mcfg, self.tcfg

        def base_loss(trainable, batch, perturb=None, rung=None):
            return lm.lm_loss(mcfg, self._to_model(trainable), batch,
                              perturb=perturb, rung=rung)

        if tcfg.n_loss_shards <= 1 or tcfg.quorum >= 1.0:
            self.loss_fn = base_loss
            return

        n_sh = tcfg.n_loss_shards
        n_ok = max(1, int(round(tcfg.quorum * n_sh)))

        def quorum_loss(trainable, batch, perturb=None):
            # deterministic straggler subset per batch content
            tag = jnp.sum(batch["labels"][:, -1]).astype(jnp.uint32)
            bits = rng.mix32(jnp.arange(n_sh, dtype=jnp.uint32) * jnp.uint32(
                0x9E3779B9) + rng.fold(tag, jnp.uint32(0xFA11)))
            arrived = jnp.argsort(bits) < n_ok          # n_ok shards arrive
            shards = jax.tree.map(
                lambda x: x.reshape(n_sh, x.shape[0] // n_sh, *x.shape[1:]),
                batch)
            # the head's window from the whole batch, outside the vmap:
            # a batched index would make XLA run every window
            rung = lm.head_rung(batch["loss_mask"])
            losses = jax.vmap(lambda b: base_loss(
                trainable, b, perturb=perturb, rung=rung))(shards)
            w = arrived.astype(jnp.float32)
            return jnp.sum(losses * w) / jnp.sum(w)

        self.loss_fn = quorum_loss

    # ------------------------------------------------------------- step
    def _build_step(self):
        if self.experiment is not None:
            import importlib
            # repro.api binds the name "validate" to the function; the
            # module itself has to come from importlib
            api_validate = importlib.import_module("repro.api.validate")
            if api_validate.swarm_active(self.experiment):
                # swarm spec (DESIGN.md §14): run the decomposed sharded
                # step — the same probe/reduce/commit programs a swarm
                # worker runs, so a lone process and an N-worker swarm
                # commit bit-identical steps on this spec.  Stateless
                # (est_state == {}), so replay's ckpt fast-forward works.
                from repro.swarm import shardstep
                self._step = shardstep.from_trainer(
                    self, api_validate.swarm_shards(self.experiment))
                self.est_state = {}
                self.fo_state = None
                self._eval_loss = jax.jit(self.loss_fn)
                return
        if self.tcfg.mode == "zo":
            step, init = estimators.make_step(self.loss_fn, self.spec,
                                              self.est_cfg)
            self._step = jax.jit(step, donate_argnums=(0, 1))
            self.est_state = init()
            self.fo_state = None
        elif self.tcfg.mode == "zo_momentum":
            mcfg = zo_adaptive.ZOMomentumConfig(
                eps=self.zo_cfg.eps, lr=self.zo_cfg.lr,
                n_drop=self.zo_cfg.n_drop)
            step, init = zo_adaptive.make_zo_momentum_step(
                self.loss_fn, self.spec, mcfg)
            self._mom_step = jax.jit(step, donate_argnums=(0, 1))
            self.mom_state = init()
            self._step = None
            self.fo_state = None
        else:
            step = fo.make_fo_step(self.loss_fn, self.fo_cfg)
            self._step = jax.jit(step, donate_argnums=(0, 1))
            self.fo_state = fo.init_state(self.trainable, self.fo_cfg)
        self._eval_loss = jax.jit(self.loss_fn)

    # ------------------------------------------------------------- data
    def make_dataset(self, n: int, seed_shift: int = 0):
        """Dataset in the synthetic batch format, from either task type."""
        if self.registry_task is not None:
            t = self.registry_task
            return t.make_dataset(n, seed=t.seed + seed_shift)
        return synthetic.make_dataset(
            dataclasses.replace(self.task, seed=self.task.seed + seed_shift)
            if seed_shift else self.task, n)

    @staticmethod
    def _model_batch(np_batch, n=None):
        """Strip eval-only keys; the loss/model sees only token arrays."""
        return {k: jnp.asarray(v if n is None else v[:n])
                for k, v in np_batch.items() if k in tasks_mod.MODEL_BATCH_KEYS}

    def _ckpt_extra(self) -> Optional[Dict[str, Any]]:
        """Spec-built trainers embed their spec in every manifest so a
        resume can verify it is replaying the same experiment."""
        if self.experiment is None:
            return None
        from repro import api
        extra = {"spec": api.to_dict(self.experiment)}
        if self.run_id is not None:
            extra["run_id"] = self.run_id
        return extra

    # ------------------------------------------------------------ train
    def train(self, train_data=None, val_data=None) -> Dict[str, Any]:
        tcfg = self.tcfg
        if train_data is None:
            train_data = self.make_dataset(4096)
        if val_data is None:
            val_data = self.make_dataset(512, seed_shift=1)
        base_seed = np.uint32(rng.fold_py(tcfg.seed, 0xC0FFEE))

        start = 0
        params = self.trainable
        if self.ckpt and self.ckpt.latest() is not None:
            if self.experiment is not None:
                from repro import api
                saved = self.ckpt.read_manifest().get(
                    "extra", {}).get("spec")
                if saved is not None:
                    # loud failure with a field diff when the checkpoint
                    # was written under a different experiment spec
                    api.check_resume_spec(saved, self.experiment)
            params, start, _, _ = self.ckpt.restore(params)
            params = jax.tree.map(jnp.asarray, params)
            # estimator state (O(scalars), e.g. importance EMA scores) is
            # not checkpointed: after resume it re-warms from init within
            # ~1/(1-decay) steps (DESIGN.md §7)

        history = {"step": [], "loss": [], "val_loss": [], "val_step": [],
                   "val_acc": [], "wall": [], "wall_compute": []}
        if self.registry_task is not None:
            history["metric_name"] = self.registry_task.metric
        # best-checkpoint score, maximized: task metric for registry tasks
        # (SuperGLUE protocol), -val_loss otherwise (the paper's protocol)
        best = (-np.inf, None, -1)
        tr = self.obs.tracer
        overhead = 0.0   # eval + checkpoint seconds, excluded from wall_compute
        t0 = time.perf_counter()
        # eval-only arrays (e.g. multiple-choice candidates) would be
        # fancy-indexed every step just to be dropped by _model_batch
        stream_data = {k: v for k, v in train_data.items()
                       if k in tasks_mod.MODEL_BATCH_KEYS}
        stream = synthetic.batches(stream_data, tcfg.batch_size, tcfg.steps,
                                   seed=tcfg.seed + 7)
        with self.obs.profile():
            for t, np_batch in enumerate(stream):
                if t < start:
                    continue
                batch = self._model_batch(np_batch)
                with tr.span(obs_mod.TRAIN_STEP) as sp:
                    if self.tcfg.mode == "zo":
                        params, self.est_state, metrics = self._step(
                            params, self.est_state, batch, jnp.int32(t),
                            base_seed)
                    elif self.tcfg.mode == "zo_momentum":
                        params, self.mom_state, metrics = self._mom_step(
                            params, self.mom_state, batch, jnp.int32(t),
                            base_seed)
                    else:
                        params, self.fo_state, metrics = self._step(
                            params, self.fo_state, batch, jnp.int32(t))
                    sp.fence(metrics["loss"])
                if tr.enabled and "active_layers" in metrics:
                    tr.gauge(obs_mod.GAUGE_ACTIVE,
                             int(metrics["active_layers"]))
                if self.health is not None:
                    # buffers device values only — no sync until drain
                    self.health.record(t, metrics,
                                       seed=rng.fold_py(int(base_seed), t))
                # the final step always logs, even off the log_every grid —
                # a truncated tail made short runs look like they never ran
                if tcfg.log_every and (t % tcfg.log_every == 0
                                       or t == tcfg.steps - 1):
                    now = time.perf_counter()
                    history["step"].append(t)
                    history["loss"].append(float(metrics["loss"]))
                    history["wall"].append(now - t0)
                    history["wall_compute"].append(now - t0 - overhead)
                    if self.runlog is not None:
                        # the float() above already synced this step; the
                        # batched device_get rides the same drain point
                        self.runlog.append(self.health.drain())
                if tcfg.eval_every and (t + 1) % tcfg.eval_every == 0:
                    te = time.perf_counter()
                    vl, va = self.evaluate(params, val_data)
                    history["val_step"].append(t + 1)
                    history["val_loss"].append(vl)
                    history["val_acc"].append(va)
                    score = va if self.registry_task is not None else -vl
                    if score > best[0]:
                        best = (score, jax.tree.map(np.asarray, params), t + 1)
                    overhead += time.perf_counter() - te
                if (self.ckpt and tcfg.ckpt_every
                        and (t + 1) % tcfg.ckpt_every == 0):
                    te = time.perf_counter()
                    self.ckpt.save(t + 1, params, int(base_seed),
                                   extra=self._ckpt_extra(), blocking=False)
                    overhead += time.perf_counter() - te
        if self.ckpt:
            self.ckpt.wait()
        history["final_params"] = params
        if best[1] is not None:
            history["best_params"] = best[1]
            history["best_step"] = best[2]
        if self.runlog is not None:
            self.runlog.append(self.health.drain())
            self.runlog.finalize(self.health.summary())
            history["run_id"] = self.run_id
            history["run_dir"] = self.runlog.dir
        self.obs.flush()
        return history

    def evaluate(self, params, val_data, max_examples=256):
        """Returns (val_loss, metric): the registry task's primary metric,
        or verbalizer accuracy for legacy synthetic tasks (-1 if n/a)."""
        n = min(max_examples, val_data["tokens"].shape[0])
        vl = float(self._eval_loss(params, self._model_batch(val_data, n)))
        if self.registry_task is not None:
            va = self.registry_task.evaluate(
                self.mcfg, self._to_model(params), val_data, lm,
                max_examples=n)
        elif self.task.kind in ("classification", "multiple_choice"):
            va = synthetic.classification_accuracy(
                self.mcfg, self._to_model(params), val_data, self.task, lm,
                max_examples=n)
        else:
            va = -1.0
        return vl, va
