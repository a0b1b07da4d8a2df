"""End-to-end: smoke-scale ZO fine-tuning beats the zero-shot baseline
on the registry's classification tasks (the paper's Table-1 ordering,
reduced to CPU scale).

Tier-2 (slow): ~250 ZO steps per task.  Single-pool lexicon tasks
(sst2, boolq, cb) are reliably learned at this scale.  rte (premise/
hypothesis overlap) and wic (same-pool-in-both-sentences, an XOR over
two lexicon indicators) both require cross-region comparison and may
stay at chance for a 2-layer smoke model — the gate is >=3 of the 5
classification tasks improving, mirroring the acceptance criterion.
"""
import dataclasses

import numpy as np
import pytest

from repro import tasks
from repro.configs import opt
from repro.core import zo
from repro.train.trainer import Trainer, TrainConfig

MCFG = opt.opt_tiny(layers=2, d_model=64, vocab=512)
SEQ = 48


def _zo_run(task, steps=300):
    tr = Trainer(MCFG, task,
                 TrainConfig(steps=steps, batch_size=32, eval_every=steps // 3,
                             log_every=0, seed=0),
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=1e-3, n_drop=1))
    val = tr.make_dataset(256, seed_shift=1)
    zs_loss, zeroshot = tr.evaluate(tr.trainable, val)
    hist = tr.train(val_data=val)
    return zs_loss, zeroshot, hist


@pytest.mark.slow
def test_zo_beats_zeroshot_on_classification_tasks():
    wins, results = 0, {}
    for name in tasks.classification_names():
        task = tasks.build(name, vocab=MCFG.vocab, seq_len=SEQ)
        _, zeroshot, hist = _zo_run(task)
        # best-checkpoint metric: the subsystem's own selection protocol
        # (ZO metric curves are non-monotone at smoke scale)
        trained = max(hist["val_acc"])
        results[name] = (zeroshot, trained)
        if trained > zeroshot + 0.02:
            wins += 1
    assert wins >= 3, f"ZO beat zero-shot on only {wins} tasks: {results}"


@pytest.mark.slow
def test_best_checkpoint_selected_on_task_metric():
    """Registry tasks select best params by highest metric, and the best
    params really do score what the history claims."""
    task = tasks.build("sst2", vocab=MCFG.vocab, seq_len=SEQ)
    tr = Trainer(MCFG, task,
                 TrainConfig(steps=200, batch_size=32, eval_every=100,
                             log_every=0),
                 zo_cfg=zo.ZOConfig(eps=1e-3, lr=1e-3, n_drop=1))
    val = tr.make_dataset(256, seed_shift=1)
    hist = tr.train(val_data=val)
    assert hist["metric_name"] == "accuracy"
    assert "best_params" in hist
    best_i = int(np.argmax(hist["val_acc"]))
    assert hist["best_step"] == hist["val_step"][best_i]
    _, best_metric = tr.evaluate(hist["best_params"], val)
    assert best_metric == pytest.approx(hist["val_acc"][best_i])


@pytest.mark.slow
def test_zo_learns_generative_copy_task():
    """squad_copy: exact-match stays a hard target at smoke scale (4
    exact tokens through a 2-layer model), so the pinned claim is the
    answer-span loss improving over zero-shot while EM never regresses."""
    task = tasks.build("squad_copy", vocab=MCFG.vocab, seq_len=SEQ)
    zs_loss, zeroshot, hist = _zo_run(task, steps=300)
    assert hist["val_loss"][-1] < zs_loss - 0.1
    assert hist["val_acc"][-1] >= zeroshot     # EM never regresses below
