"""Decomposed sharded ZO step — the swarm's unit of execution
(DESIGN.md §14).

The monolithic jitted trainer step cannot be bit-reproduced by a
multi-process swarm: XLA fuses the probe/reduce/update into one graph
whose FMA contraction depends on the graph's shape (see
``launch/replay.py`` — even a standalone update axpy differs by ~1 ULP).
So when the spec's ``swarm`` node is active, **both** the single-process
trainer and every swarm worker run this decomposed step instead:

1. ``probe(params, shard_batch, seed) -> (l+, l-)`` — one jitted ±εz
   two-point probe per loss shard.  Never mutates ``params`` (the
   materialized path perturbs, probes and discards inside the jit), so
   the parameter trajectory is a pure fold of commits over the
   ``(seed, g)`` log — which is exactly what lets a replacement worker
   reconstruct params from ``steps.jsonl`` without weight transfer.
2. a host-side float32 reduction in fixed shard order
   (:mod:`repro.swarm.commit`) — identical bits no matter which process
   evaluated which shard, or in what order contributions arrived.
3. ``commit(params, seed, g)`` — one jitted, donated update axpy.

The shard count is fixed by the *spec* (``api.validate.swarm_shards``),
not by live membership, so a 1-, 2- and 4-worker swarm — and a lone
``launch train`` — commit byte-identical steps on the same spec.
``arrived`` (quorum fallback) is an explicit input, recorded per step
and replayed from the run log.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import estimators
from repro.core import rng, zo
from repro.swarm import commit as commit_mod


def shard_batch(batch, n_shards: int) -> List[dict]:
    """Split a batch dict into ``n_shards`` contiguous equal slices
    along axis 0 — shard i is rows ``[i·B/n, (i+1)·B/n)``, the same
    fixed assignment everywhere."""
    n = next(iter(batch.values())).shape[0]
    if n % n_shards:
        raise ValueError(f"batch of {n} does not divide into "
                         f"{n_shards} shards")
    per = n // n_shards
    return [{k: v[i * per:(i + 1) * per] for k, v in batch.items()}
            for i in range(n_shards)]


class ShardedZOStep:
    """Drop-in for the trainer's jitted ``_step`` on swarm specs.

    ``__call__(params, state, batch, step_idx, base_seed, arrived=None)
    -> (params, state, metrics)`` — the trainer's step interface, plus
    the quorum mask.  ``state`` is the empty dict (two_point is
    stateless), which keeps ``launch replay``'s stateless fast-forward
    path working.  Metrics come back as host numpy scalars plus the
    selection metrics as device arrays; rows gain ``arrived`` and
    ``shard_losses`` so a quorum-degraded commit replays exactly.
    """

    sharded = True

    def __init__(self, loss_fn, zspec: zo.ZOSpec,
                 cfg: estimators.EstimatorConfig, n_shards: int,
                 shapes: Sequence):
        if cfg.name != "two_point":
            raise ValueError("the sharded step carries one (l+, l-) pair "
                             f"per shard — two_point only, got {cfg.name!r}")
        self.n_shards = int(n_shards)
        self.cfg = cfg
        self.zspec = zspec
        est = estimators.build_estimator(zspec, cfg)
        self.est = est

        def probe(params, shard, seed, step):
            masks, idxs, _ = est.select(seed, {}, step)
            if est.virtual and cfg.paired_probes:
                losses = est._vloss_pair(loss_fn, params, shard, seed,
                                         cfg.eps, masks)
                return jnp.stack([losses[0], losses[1]])
            if est.virtual:
                lp = est._vloss(loss_fn, params, shard, seed, cfg.eps, masks)
                lm = est._vloss(loss_fn, params, shard, seed, -cfg.eps, masks)
                return jnp.stack([lp, lm])
            p = est._ax(params, cfg.eps, seed, idxs)
            lp = loss_fn(p, shard)
            p = est._ax(p, -2.0 * cfg.eps, seed, idxs)
            lm = loss_fn(p, shard)
            # p (= params - eps*z) dies here: probes never mutate params
            return jnp.stack([lp, lm])

        def commit(params, seed, g, step):
            _, idxs, _ = est.select(seed, {}, step)
            decay = 1.0 - cfg.lr * cfg.weight_decay
            return est._ax(params, -jnp.float32(cfg.lr) * g, seed, idxs,
                           decay)

        def sel_metrics(seed, step):
            masks, _, n_active = est.select(seed, {}, step)
            out = {
                "active_layers": jnp.asarray(n_active, jnp.int32),
                "n_active_params": jnp.stack(
                    [zo.active_param_count(zspec, tuple(shapes), masks)]),
            }
            if zspec.num_layers:
                out["layer_sel"] = zo.global_layer_mask(
                    zspec, masks).astype(jnp.int32)
            return out

        self._probe = jax.jit(probe)
        self._commit = jax.jit(commit, donate_argnums=(0,))
        self._sel_metrics = jax.jit(sel_metrics)

    # ------------------------------------------------------ shard-level
    def probe_shard(self, params, shard, seed: int, step: int) -> np.ndarray:
        """(l+, l-) for one shard of step ``step`` as host float32 — what
        a worker puts in its :class:`~repro.swarm.proto.StepContribution`."""
        return np.asarray(self._probe(params, shard, jnp.uint32(seed),
                                      jnp.int32(step)), np.float32)

    def apply_commit(self, params, seed: int, g: float, step: int):
        """Fold one committed ``(seed, g)`` of step ``step`` into params —
        the elastic fast-forward primitive (donates the old params)."""
        return self._commit(params, jnp.uint32(seed), jnp.float32(g),
                            jnp.int32(step))

    def selection_metrics(self, seed: int, step: int) -> Dict:
        """The layer-selection health scalars for a committed step;
        pure function of its seed and index — no parameters involved."""
        return dict(self._sel_metrics(jnp.uint32(seed), jnp.int32(step)))

    # ------------------------------------------------------- trainer API
    def __call__(self, params, state, batch, step_idx, base_seed,
                 arrived: Optional[Sequence[int]] = None):
        t = int(step_idx)
        seed = rng.fold_py(int(base_seed), t)
        shards = shard_batch(batch, self.n_shards)
        if arrived is None:
            arrived = [1] * self.n_shards
        if len(arrived) != self.n_shards:
            raise ValueError(f"arrived mask of {len(arrived)} for "
                             f"{self.n_shards} shards")
        # dispatch every arrived probe before fetching any — the host
        # reduction then drains them in fixed shard order
        pending = {i: self._probe(params, shards[i], jnp.uint32(seed),
                                  jnp.int32(t))
                   for i in range(self.n_shards) if arrived[i]}
        pairs = [np.asarray(pending[i], np.float32) if i in pending else None
                 for i in range(self.n_shards)]
        scal = commit_mod.commit_scalars(pairs, self.cfg.eps)
        g = scal["projected_grad"]
        params = self.apply_commit(params, seed, g, t)
        metrics = {
            "loss": scal["loss"],
            "projected_grad": g,
            "probe_grads": np.asarray([g], np.float32),
            "coeffs": np.asarray([g], np.float32),
            "eps": np.float32(self.cfg.eps),
            "lr": float(self.cfg.lr),
            "arrived": np.asarray(scal["arrived"], np.int32),
            "shard_losses": commit_mod.shard_losses_dict(pairs),
        }
        metrics.update(self.selection_metrics(seed, t))
        return params, state, metrics


def from_trainer(trainer, n_shards: int) -> ShardedZOStep:
    """The trainer hook: build the sharded step from an already-built
    Trainer's loss/spec/config (``Trainer._build_step`` calls this when
    the experiment's swarm node is active)."""
    return ShardedZOStep(trainer.loss_fn, trainer.spec, trainer.est_cfg,
                         n_shards, zo.leaf_shapes(trainer.trainable))


# --------------------------------------------------- paramless builders
def abstract_trainable(experiment):
    """The trainable pytree as ShapeDtypeStructs + its ZO group_fn —
    via ``jax.eval_shape``, so the coordinator (which never holds
    parameters) can build selection metrics and z-norms for free."""
    from repro import api
    from repro.models import lm
    from repro.peft import lora as lora_mod
    from repro.peft import prefix as prefix_mod

    d = api.derive(experiment)
    tcfg, mcfg = d.tcfg, d.model_cfg

    if tcfg.peft == "lora":
        def init(seed0):
            key = jax.random.PRNGKey(seed0)
            return lora_mod.init_lora(lm.init_params(mcfg, key), d.lora_cfg,
                                      jax.random.fold_in(key, 1))
        group_fn = lora_mod.lora_group_fn
    elif tcfg.peft == "prefix":
        def init(seed0):
            key = jax.random.PRNGKey(seed0)
            return prefix_mod.init_prefix(mcfg, jax.random.fold_in(key, 2),
                                          d.prefix_cfg)
        group_fn = prefix_mod.prefix_group_fn
    else:
        def init(seed0):
            return lm.init_params(mcfg, jax.random.PRNGKey(seed0))
        group_fn = lm.zo_group_fn

    tr = jax.eval_shape(init, jnp.int32(tcfg.seed))
    return tr, group_fn, d


def trainable_param_count(experiment) -> int:
    """Total trainable parameters — the FO all-reduce baseline is
    ``4 · this`` bytes per step (float32 gradients)."""
    tr, _, _ = abstract_trainable(experiment)
    return int(sum(int(np.prod(s)) for s in zo.leaf_shapes(tr)))


class SelectionOracle:
    """Coordinator-side seed -> health metrics, built without params.

    Wraps the same jitted selection program as :class:`ShardedZOStep`
    plus (optionally) the exact ‖z‖ norm fn the trainer uses for
    ``telemetry.health_norms`` — all shape-only, from the abstract
    trainable.
    """

    def __init__(self, experiment):
        tr, group_fn, d = abstract_trainable(experiment)
        self.zspec = zo.build_spec(tr, group_fn)
        self.shapes = zo.leaf_shapes(tr)
        self.est_cfg = d.est_cfg
        est = estimators.build_estimator(self.zspec, d.est_cfg)
        zspec, shapes = self.zspec, self.shapes

        def sel_metrics(seed, step):
            masks, _, n_active = est.select(seed, {}, step)
            out = {
                "active_layers": jnp.asarray(n_active, jnp.int32),
                "n_active_params": jnp.stack(
                    [zo.active_param_count(zspec, tuple(shapes), masks)]),
            }
            if zspec.num_layers:
                out["layer_sel"] = zo.global_layer_mask(
                    zspec, masks).astype(jnp.int32)
            return out

        self._sel_metrics = jax.jit(sel_metrics)

        @jax.jit
        def znorm(seed, gmask):
            return zo.tree_z_norm(zspec, shapes, seed,
                                  zspec.split_mask(gmask))

        def norm_fn(seed, layer_sel):
            gmask = jnp.asarray(np.asarray(layer_sel) > 0)
            return float(znorm(jnp.uint32(seed), gmask))

        self.norm_fn = norm_fn if self.zspec.num_layers else None

    @property
    def num_layers(self) -> int:
        return self.zspec.num_layers or 0

    def metrics(self, seed: int, step: int) -> Dict:
        return dict(self._sel_metrics(jnp.uint32(seed), jnp.int32(step)))
