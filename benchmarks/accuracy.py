"""Paper Tables 1/2/3, generalized: the estimator x task accuracy matrix.

Sweeps the task registry (repro/tasks/: SuperGLUE stand-ins, DESIGN.md
§9) against the estimator registry (repro/estimators/) at LeZO sparsity,
plus MeZO (n_drop=0), the FO(AdamW) ceiling, and the zero-shot floor.
The reproducible claim is the ORDERING per task:

    zero-shot  <  ZO estimators (LeZO ~= MeZO)  <=  FO

with per-task metrics following the SuperGLUE protocol (accuracy,
macro-F1 for cb, exact-match for squad_copy).

``--smoke`` shrinks steps/tasks for CI-speed sanity runs.
"""
from __future__ import annotations

import sys

from benchmarks.common import emit
from repro import tasks
from repro.configs import opt
from repro.core import fo, zo
from repro.train.trainer import Trainer, TrainConfig

MCFG = opt.opt_tiny(layers=4, d_model=128, vocab=512)
STEPS = 500
SEQ = 48

# (row label, mode, estimator, q, n_drop)
OPTIMIZERS = (
    ("mezo", "zo", "two_point", 1, 0),
    ("lezo50", "zo", "two_point", 1, 2),
    ("lezo50_one_sided_q4", "zo", "one_sided", 4, 2),
    ("lezo50_averaged_q4", "zo", "averaged", 4, 2),
    ("ft_adamw", "fo", "two_point", 1, 0),
)


def _train(task, mode, estimator, q, n_drop, steps, seed=0):
    zo_steps = steps if mode == "zo" else max(60, steps // 5)
    tcfg = TrainConfig(steps=zo_steps, batch_size=32, eval_every=zo_steps,
                       log_every=0, mode=mode, seed=seed,
                       estimator=estimator, est_q=q)
    tr = Trainer(MCFG, task, tcfg,
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=1e-3, n_drop=n_drop),
                 fo_cfg=fo.FOConfig(lr=5e-4))
    h = tr.train()
    metric = h["val_acc"][-1] if h["val_acc"] else -1.0
    vloss = h["val_loss"][-1] if h["val_loss"] else float("inf")
    return metric, vloss


def run(smoke: bool = False):
    steps = 100 if smoke else STEPS
    names = ("sst2", "copa") if smoke else tasks.names()
    optimizers = OPTIMIZERS[:2] + OPTIMIZERS[-1:] if smoke else OPTIMIZERS
    rows = []
    for tname in names:
        task = tasks.build(tname, vocab=MCFG.vocab, seq_len=SEQ)
        # average the zero-shot floor over a few inits: at tiny d_model a
        # single random init can score far off 1/k through tied-embedding
        # luck, which would misstate the ordering claim
        zs_metrics, zs_losses = [], []
        val = None
        for s in range(3):
            zs = Trainer(MCFG, task, TrainConfig(steps=1, batch_size=4,
                                                 eval_every=0, log_every=0,
                                                 seed=s))
            if val is None:      # val set depends on the task, not the seed
                val = zs.make_dataset(256, seed_shift=1)
            l, m = zs.evaluate(zs.trainable, val)
            zs_losses.append(l)
            zs_metrics.append(m)
        rows.append((f"{tname}_zeroshot", 0.0,
                     f"{task.metric}={sum(zs_metrics) / 3:.3f} "
                     f"loss={sum(zs_losses) / 3:.3f}"))
        for label, mode, est, q, nd in optimizers:
            metric, vl = _train(task, mode, est, q, nd, steps)
            rows.append((f"{tname}_{label}", 0.0,
                         f"{task.metric}={metric:.3f} loss={vl:.3f}"))
    return emit(rows)


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv)
